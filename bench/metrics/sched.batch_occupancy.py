"""Scheduler: lanes decoding per decode step over ``max_batch``,
weighted by the steps each fused tick ran, from the program's
``fused_tick`` and ``decode_step`` events in the window.  Moves
``output_tok_s``."""


def read(run):
    lanes = steps = 0
    for e in run.events:
        if e.kind == "fused_tick":
            lanes += e.args["batch"] * e.args["horizon"]
            steps += e.args["horizon"]
        elif e.kind == "decode_step":
            lanes += e.args["batch"]
            steps += 1
    return lanes / steps / run.max_batch if steps else None
