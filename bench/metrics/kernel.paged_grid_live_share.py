"""Kernel: the share of the paged attention kernel's grid that touched
a live page, over the window: the program's ``live_pages`` over its
``grid_pages``, summed over its ``fused_tick`` and ``decode_step``
events (lane-page units per layer per KV head).  The kernel walks
``max_batch x nblk`` pages at every scan step whatever the context, so
the rest is dead grid.  A program whose events carry no such counts
reads nothing.  Moves ``tpot_p90_ms``."""


def read(run):
    live = grid = 0
    for e in run.events:
        if e.kind in ("fused_tick", "decode_step"):
            if "grid_pages" not in e.args:
                return None
            live += e.args["live_pages"]
            grid += e.args["grid_pages"]
    return live / grid if grid else None
