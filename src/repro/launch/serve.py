"""Serving driver: continuous-batching decode on the real model.

  PYTHONPATH=src python -m repro.launch.serve --arch yi-6b \
      --rate 4 --n-requests 12 --prompt-len 32     # reduced config default

``--pallas`` routes decode attention through the flash-decode Pallas kernel
(compiled on the TPU; Pallas interpret mode on the CPU backend, where the
tests run); with ``--paged`` it becomes the block-table read-through
paged kernel.  ``--paged`` switches KV residency to the page-pool layout
(``--page-size``, ``--num-pages`` to oversubscribe) and
``--prefill-chunk`` interleaves Sarathi prefill chunks with the hot
decode batch (written directly into block-table pages on the paged
engine).  ``--fuse-steps K`` (paged only) fuses up to K decode steps
into one device-resident ``lax.scan`` tick — the host surfaces only at
fusion-horizon boundaries; tokens are identical to per-tick dispatch.
``--prefix-sharing`` adds refcounted prompt-prefix pages with
copy-on-write; combine it with ``--shared-prefix N`` to drive a
shared-system-prompt trace (every prompt = N common tokens + a unique
tail) and watch the dedup ratio in the report.  ``--placement
{free-first,interleave,affinity}`` partitions the page pool into
per-channel regions (``--placement-regions``) and reports the
block-table gather cost against the SNAKE substrate.

``--codesign`` turns on live array-shape/dataflow co-design pricing:
every tick's actual composition (decode batch, per-slot contexts, the
in-flight prefill chunk) is scheduled on the SNAKE substrate model and
the report gains ``modeled_tokens_per_s`` / ``reconfigurations`` /
``array_util_mean`` (``--codesign-rows R`` prices a fixed RxC array
baseline instead).

Multi-replica serving (PR 3): ``--replicas N`` stands up N engine
replicas behind the front-end router and ``--router-policy`` picks the
dispatch policy (``round_robin`` / ``least_loaded`` /
``session_affinity`` / ``prefix_affinity`` — the latter routes requests
to the replica whose prefix trie already holds their leading prompt
pages).  ``--groups G`` drives a skewed multi-tenant trace (G distinct
system prompts, Zipf popularity).  ``--eos-rate`` samples per-request
early-stop decode lengths; ``--trace-file`` replays a recorded JSON
trace instead of synthesizing one.

Disaggregated serving (PR 10): ``--tiers P:D`` (with ``--paged`` and
``--replicas P+D``) splits the cluster into a prefill tier and a decode
tier; finished prefills are shipped — KV pages, block-table row, prefix
coverage — over the priced inter-stack link, and ``ship`` events land
in the ``--trace-out`` timeline.
"""
from __future__ import annotations

import argparse

from repro.launch.compile_cache import enable_compile_cache
from repro.models import registry
from repro.serving.engine import (EngineConfig, load_trace, make_engine,
                                  make_grouped_prefix_trace,
                                  make_shared_prefix_trace, make_trace)
from repro.serving.router import POLICIES, make_cluster


def build_trace(args, vocab: int):
    if args.trace_file:
        return load_trace(args.trace_file, vocab=vocab)
    if args.shared_prefix > 0:
        # total prompt length stays --prompt-len: N shared + unique tail
        prefix = min(args.shared_prefix, args.prompt_len - 1)
        if args.groups > 1:
            return make_grouped_prefix_trace(
                vocab, rate_req_s=args.rate, n_requests=args.n_requests,
                n_groups=args.groups, prefix_len=prefix,
                tail_len=args.prompt_len - prefix, skew=args.group_skew,
                eos_rate=args.eos_rate)
        return make_shared_prefix_trace(
            vocab, rate_req_s=args.rate, n_requests=args.n_requests,
            prefix_len=prefix, tail_len=args.prompt_len - prefix,
            eos_rate=args.eos_rate)
    return make_trace(vocab, rate_req_s=args.rate,
                      n_requests=args.n_requests,
                      prompt_len=args.prompt_len, eos_rate=args.eos_rate)


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", choices=registry.ARCH_IDS + registry.EXTRA_ARCH_IDS, default="yi-6b")
    ap.add_argument("--rate", type=float, default=4.0)
    ap.add_argument("--n-requests", type=int, default=12)
    ap.add_argument("--prompt-len", type=int, default=32)
    ap.add_argument("--max-new", type=int, default=16)
    ap.add_argument("--max-batch", type=int, default=4)
    ap.add_argument("--full", action="store_true")
    ap.add_argument("--pallas", action="store_true",
                    help="flash-decode Pallas kernel for decode attention")
    ap.add_argument("--prefill-chunk", type=int, default=None,
                    help="Sarathi-style chunked prefill")
    ap.add_argument("--paged", action="store_true",
                    help="block-table paged KV cache")
    ap.add_argument("--page-size", type=int, default=16)
    ap.add_argument("--num-pages", type=int, default=None,
                    help="page-pool size (oversubscribe below the dense-"
                         "equivalent capacity to exercise preemption)")
    ap.add_argument("--prefix-sharing", action="store_true",
                    help="refcounted prompt-prefix page sharing + CoW")
    ap.add_argument("--fuse-steps", type=int, default=1,
                    help="decode steps fused into one device-resident "
                         "lax.scan (1: per-tick dispatch; the realized "
                         "horizon is clipped by page windows and decode "
                         "budgets, so tokens are identical either way)")
    ap.add_argument("--shared-prefix", type=int, default=0,
                    help="common system-prompt tokens per request "
                         "(0: fully unique prompts)")
    ap.add_argument("--defrag-threshold", type=float, default=0.5,
                    help="fragmentation fraction that triggers pool "
                         "defrag (negative disables)")
    ap.add_argument("--placement", default=None,
                    choices=["free-first", "interleave", "affinity"],
                    help="stack-aware page placement: partition the page "
                         "pool into per-channel regions and co-locate "
                         "(affinity) or stripe (interleave) each slot's "
                         "pages; free-first keeps the legacy layout but "
                         "reports its gather cost")
    ap.add_argument("--placement-regions", type=int, default=None,
                    help="per-channel regions (default: one per PU of "
                         "the SNAKE template, capped by pool size)")
    ap.add_argument("--replicas", type=int, default=1,
                    help="engine replicas behind the front-end router")
    ap.add_argument("--router-policy", choices=POLICIES,
                    default="round_robin")
    ap.add_argument("--tiers", type=str, default=None, metavar="P:D",
                    help="disaggregate the cluster into P prefill and D "
                         "decode replicas (P+D must equal --replicas; "
                         "requires --paged): prefills are harvested at "
                         "completion and their KV pages shipped over the "
                         "priced inter-stack link to the decode tier")
    ap.add_argument("--groups", type=int, default=1,
                    help="distinct system-prompt groups (with "
                         "--shared-prefix): the prefix-affinity workload")
    ap.add_argument("--group-skew", type=float, default=1.0,
                    help="Zipf popularity skew across groups")
    ap.add_argument("--codesign", action="store_true",
                    help="price every tick's batch composition on the "
                         "SNAKE substrate model (live array-shape/"
                         "dataflow co-design) and report the modeled "
                         "throughput, reconfiguration count, and array "
                         "utilization next to the wall-clock metrics")
    ap.add_argument("--codesign-rows", type=int, default=None,
                    choices=[8, 16, 32, 64],
                    help="price a fixed rows x (4096/rows) array instead "
                         "of the reconfigurable SNAKE substrate")
    ap.add_argument("--reconfig-cost", type=float, default=None,
                    metavar="SECONDS",
                    help="modeled-clock charge per substrate "
                         "reconfiguration (shape-profile change); "
                         "default derives the pipeline fill/drain cost "
                         "from the array geometry")
    ap.add_argument("--eos-rate", type=float, default=None,
                    help="per-step early-stop probability (samples "
                         "per-request decode budgets)")
    ap.add_argument("--trace-file", type=str, default=None,
                    help="replay a recorded JSON trace "
                         "(serving.scheduler.load_trace format)")
    ap.add_argument("--trace-out", type=str, default=None,
                    metavar="PERFETTO_JSON",
                    help="write a Perfetto/chrome-trace timeline of the "
                         "run (open at https://ui.perfetto.dev); a "
                         "lossless .jsonl event log is written next to "
                         "it and the phase-attribution report is printed")
    args = ap.parse_args()
    if args.prefix_sharing and not args.paged:
        ap.error("--prefix-sharing requires --paged (the dense engine "
                 "has no page tables to share)")
    if args.router_policy == "prefix_affinity" and not args.prefix_sharing:
        ap.error("--router-policy prefix_affinity requires "
                 "--prefix-sharing (nothing resident to probe otherwise)")
    if args.placement and not args.paged:
        ap.error("--placement requires --paged (the dense cache has no "
                 "page pool to partition)")
    if args.fuse_steps > 1 and not args.paged:
        ap.error("--fuse-steps requires --paged (the fused scan runs on "
                 "the block-table decode step)")
    if args.fuse_steps < 1:
        ap.error("--fuse-steps must be >= 1")
    if args.codesign_rows and not args.codesign:
        ap.error("--codesign-rows requires --codesign")
    if args.reconfig_cost is not None and not args.codesign:
        ap.error("--reconfig-cost requires --codesign (there is no "
                 "modeled clock to charge otherwise)")
    if args.reconfig_cost is not None and args.reconfig_cost < 0:
        ap.error("--reconfig-cost must be >= 0")
    tiers = None
    if args.tiers is not None:
        try:
            p_n, d_n = (int(v) for v in args.tiers.split(":"))
        except ValueError:
            ap.error("--tiers must look like P:D, e.g. 1:3")
        if not args.paged:
            ap.error("--tiers requires --paged (page shipping moves "
                     "block-table pages)")
        if p_n < 1 or d_n < 1:
            ap.error("--tiers needs at least one replica per tier")
        if p_n + d_n != args.replicas:
            ap.error(f"--tiers {p_n}:{d_n} must sum to --replicas "
                     f"({args.replicas})")
        tiers = (p_n, d_n)

    enable_compile_cache()
    entry = registry.get(args.arch, reduced=not args.full)
    ecfg = EngineConfig(max_batch=args.max_batch,
                        max_seq=args.prompt_len + args.max_new + 2,
                        max_new_tokens=args.max_new,
                        use_pallas_decode=args.pallas,
                        prefill_chunk=args.prefill_chunk,
                        paged=args.paged,
                        page_size=args.page_size,
                        num_pages=args.num_pages,
                        prefix_sharing=args.prefix_sharing,
                        fuse_steps=args.fuse_steps,
                        defrag_threshold=(None if args.defrag_threshold < 0
                                          else args.defrag_threshold),
                        placement=args.placement,
                        placement_regions=args.placement_regions,
                        codesign=args.codesign,
                        codesign_rows=args.codesign_rows,
                        codesign_reconfig_cost_s=args.reconfig_cost)
    reqs = build_trace(args, entry.config.vocab)
    tracer = None
    if args.trace_out:
        from repro.obs import Tracer
        tracer = Tracer()
    if args.replicas > 1:
        router = make_cluster(entry, ecfg, args.replicas,
                              policy=args.router_policy, tiers=tiers)
        if tracer is not None:
            router.set_tracer(tracer)
        metrics = router.run_trace(reqs)
        per = metrics.pop("per_replica")
        print(f"[serve] {args.arch} x{args.replicas} "
              f"({args.router_policy}): {metrics}")
        for rep in per:
            print(f"[serve]   replica {rep['replica']}: {rep}")
    else:
        eng = make_engine(entry, ecfg)
        if tracer is not None:
            eng.set_tracer(tracer)
        metrics = eng.run_trace(reqs)
        print(f"[serve] {args.arch}: {metrics}")
    if tracer is not None:
        from repro.obs import export_perfetto, save_jsonl, trace_report
        export_perfetto(tracer.events, args.trace_out)
        jsonl = args.trace_out + ".jsonl"
        save_jsonl(tracer.events, jsonl)
        rep = trace_report(tracer.events)
        print(f"[serve] trace: {len(tracer.events)} events -> "
              f"{args.trace_out} (+ {jsonl})")
        print(f"[serve] phases: {rep['phases']} "
              f"makespan={rep['makespan_s']:.3f}s")


if __name__ == "__main__":
    main()
