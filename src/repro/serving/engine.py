"""Continuous-batching serving engine (the real-JAX counterpart of the
paper's Duplex-style serving simulator in ``repro.core.serving_sim``).

Two KV/state residency modes:

* **Dense (seed layout)** — the engine owns a ``max_batch``-deep cache
  pytree reserved at ``max_batch x max_seq``; finished requests free their
  slot and newly prefilled requests are inserted with a donated
  dynamic-update.
* **Paged (block-table layout, ``EngineConfig.paged``)** — sequence-bearing
  cache leaves live in a page pool (``serving/paged_cache.py``) and each
  slot maps its context through a block table, so resident KV is
  proportional to the *actual* context lengths.  Prompt pages are reserved
  at admission; decode growth allocates on demand, and when the pool is
  oversubscribed the youngest active request is preempted and re-queued.
  The decode step either gathers the slot pages into the dense view
  (reference path, token-exact vs. the dense engine) or — with
  ``use_pallas_decode`` on attention families — reads pages directly
  through the block table with the paged flash-decode kernel, never
  materializing a contiguous cache.

Admission is arrival-driven and prefill can be **chunk-interleaved**
(Sarathi, the paper's ref [1]): with ``prefill_chunk`` set, the driver
advances at most one prompt chunk via ``transformer.extend_step`` between
decode iterations, so a long prompt never stalls the hot decode batch for
more than one chunk of work.  On the paged engine those chunks are
written **directly into block-table pages** (gather the slot window,
extend, scatter the chunk) — no dense per-request staging buffer, no
admission-time copy.

The trace-driving loop itself lives in ``serving/scheduler.py`` (PR 3):
an engine is one *replica* exposing ``admit`` / ``tick`` /
``load_report``, and ``serving/router.py`` dispatches traffic across N
replicas.  ``run_trace`` / ``run_workload`` here are thin wrappers that
drive a single-replica :class:`~repro.serving.scheduler.Scheduler`.

Works for every registry family (KVCache / RWKVState / RGState /
EncDecCache) via a generic batch-axis rule: rank-1 state leaves batch on
axis 0, higher-rank leaves on axis 1 (layer dim leads).  Recurrent
families have no sequence leaves, so their paged cache degenerates to the
(already proportional) slot-dense layout.

An engine lives on one device (``device``, default the first): its
parameters are built there by a jitted ``init``, and its cache or page
pool and block tables are placed there too, so replicas behind the router
can each hold their own chip.  On CPU this drives reduced configs
end-to-end (see examples/serve_decode.py and launch/serve.py).
"""
from __future__ import annotations

import functools
import time
from dataclasses import dataclass
from typing import Any, Dict, List, Optional

import jax
import jax.numpy as jnp
import numpy as np

from repro.models import registry
from repro.obs.tracer import NULL_TRACER
from repro.serving.paged_cache import PagedCache, PageShipment, num_blocks
from repro.serving.replica_api import LoadReport
# re-exported for back-compat: these lived here before the scheduling
# loop was extracted into serving/scheduler.py
from repro.serving.scheduler import (RequestState, Scheduler, load_trace,
                                     make_grouped_prefix_trace,
                                     make_shared_prefix_trace, make_trace,
                                     save_trace)

__all__ = ["EngineConfig", "RequestState", "ServingEngine",
           "PagedServingEngine", "make_engine", "make_trace",
           "make_shared_prefix_trace", "make_grouped_prefix_trace",
           "load_trace", "save_trace", "Scheduler"]

# transformer-module families: chunkable prefill (extend_step) and the
# flash-decode attention paths all key off this one set
_ATTN_FAMILIES = ("dense", "moe", "vlm")


@dataclass
class EngineConfig:
    max_batch: int = 8
    max_seq: int = 512
    max_new_tokens: int = 32
    eos_id: int = -1            # <0: never stops early (synthetic load)
    use_pallas_decode: bool = False   # flash-decode kernel for attention
    prefill_chunk: Optional[int] = None   # Sarathi-style chunked prefill
    paged: bool = False               # block-table KV residency
    page_size: int = 16
    num_pages: Optional[int] = None   # default: dense-equivalent capacity
    prefix_sharing: bool = False      # refcounted prompt-prefix pages + CoW
    # run PagedCache.defrag() when the fraction of holes below the
    # high-water page index exceeds this (None disables the trigger)
    defrag_threshold: Optional[float] = 0.5
    # stack-aware page placement (core/placement.py): None keeps the
    # legacy layout with no region accounting; "free-first" keeps the
    # legacy layout but scores it; "affinity" co-locates a slot's pages
    # in one per-channel region; "interleave" stripes them
    placement: Optional[str] = None
    placement_regions: Optional[int] = None   # default: one per PU, capped
    # fraction of the pool carved off for shared prefix pages (placement
    # + prefix_sharing only)
    communal_frac: float = 0.25
    # live microarchitecture-scheduling co-design (core/serving_sim.py
    # TickLatencyModel): price every tick's actual operator mix on an NMP
    # substrate model and report the chosen array shapes, reconfiguration
    # count, and utilization alongside the wall-clock metrics.  The
    # modeled clock is an accounting channel — scheduling stays
    # wall-clock-driven, so decoded tokens are identical with it on/off.
    codesign: bool = False
    # price a fixed-shape substrate (rows x PEs/rows @ the same PE count
    # as the reconfigurable default) instead — the benchmark's baselines
    codesign_rows: Optional[int] = None
    # price this ModelSpec instead of the engine's own (reduced test
    # configs run tiny weights; pricing the full-size registry spec keeps
    # the substrate comparison at deployment scale), and at this tensor-
    # parallel width (the paper's stacks are tp=8 even when the reduced
    # engine itself runs tp=1)
    codesign_spec: Optional[object] = None
    codesign_tp: Optional[int] = None
    # seconds charged to the modeled clock per substrate reconfiguration
    # (shape-profile change); None derives the pipeline fill/drain cost
    # from the substrate geometry
    codesign_reconfig_cost_s: Optional[float] = None
    # fuse up to K decode steps into one jitted lax.scan with tokens,
    # lengths, and eos/finish masks resident on device (paged engine
    # only; 1 keeps the per-tick host loop).  The actual horizon each
    # tick is min(fuse_steps, steps-until-any-slot-needs-a-new-page,
    # min-remaining-decode-budget), so allocation and token streams stay
    # exactly identical to the per-tick engine
    fuse_steps: int = 1


@functools.partial(jax.jit, static_argnums=(0, 2, 3))
def _init_params(module, key, cfg, tp: int):
    """``module.init`` compiled, so each weight is written once, in its own
    dtype, on the device that holds ``key``.  Run eagerly, init built every
    stacked matrix as float32 first: a 5.8 GB ``w_down`` at Yi-6B width."""
    return module.init(key, cfg, tp)


def _insert_slot(cache, new, slot: int):
    """Write request-0 of ``new`` (batch=1 prefill output) into ``slot``."""
    def one(c, n):
        if c.ndim == 1:                       # lengths-like, batch axis 0
            return c.at[slot].set(n[0])
        return c.at[:, slot].set(n[:, 0])     # (L, B, ...) batch axis 1
    return jax.tree.map(one, cache, new)


class ServingEngine:
    """Fixed-slot dense-cache engine (the seed layout)."""

    def __init__(self, entry: registry.ArchEntry, ecfg: EngineConfig,
                 tp: int = 1, device=None):
        self.entry = entry
        self.cfg = entry.config
        self.ecfg = ecfg
        self.tp = tp
        self.device = device if device is not None else jax.devices()[0]
        key = jax.device_put(jax.random.PRNGKey(0), self.device)
        self.params = _init_params(entry.module, key, self.cfg, tp)
        self.free_slots = list(range(ecfg.max_batch))
        self.active: Dict[int, RequestState] = {}
        self.completed: List[RequestState] = []
        self.preemption_count = 0
        self.requeue: List[RequestState] = []   # preempted, awaiting re-admit
        self._prefilling: Optional[dict] = None   # chunk-scheduler state
        # disaggregation tier (PR 10): "mixed" engines prefill AND decode
        # (the colocated default); a "prefill"-tier engine runs prompts
        # but never decodes — its finished slots are harvested by the
        # router and shipped to a "decode"-tier replica's page pool
        self.role = "mixed"
        # lifecycle tracing (repro.obs): NULL_TRACER keeps the hot path
        # branch-cheap; set_tracer swaps in a recording tracer.  Tracing
        # only reads state, so tokens are bit-identical either way.
        self.tracer = NULL_TRACER
        self._init_cache()
        self._init_codesign()

        attn_fn = None
        if ecfg.use_pallas_decode and self.cfg.family in _ATTN_FAMILIES:
            from repro.kernels import ops as kops
            attn_fn = (lambda q, k, v, lengths:
                       kops.attention_decode(q, k, v, lengths))

        mod, cfg = entry.module, self.cfg

        # named programs: the device trace's XLA modules read
        # jit_engine_*, and the host's dispatch events PjitFunction(engine_*)
        def engine_prefill(params, tokens):
            if cfg.family == "audio":
                return mod.prefill(params, cfg, tokens,
                                   frames=jnp.zeros((tokens.shape[0],
                                                     cfg.encoder_frames,
                                                     cfg.d_model),
                                                    jnp.float32),
                                   tp=tp, max_seq=ecfg.max_seq)
            if cfg.family in _ATTN_FAMILIES:
                return mod.prefill(params, cfg, tokens, tp=tp,
                                   max_seq=ecfg.max_seq,
                                   chunk=ecfg.prefill_chunk)
            return mod.prefill(params, cfg, tokens, tp=tp,
                               max_seq=ecfg.max_seq)

        def engine_decode(params, cache, tokens):
            if attn_fn is not None:
                return mod.decode_step(params, cfg, tokens, cache, tp=tp,
                                       attn_fn=attn_fn)
            return mod.decode_step(params, cfg, tokens, cache, tp=tp)

        def engine_extend(params, tokens, cache):
            return mod.extend_step(params, cfg, tokens, cache, tp=tp)

        self._prefill = jax.jit(engine_prefill)
        self._decode = jax.jit(engine_decode, donate_argnums=(1,))
        if cfg.family in _ATTN_FAMILIES:
            self._extend = jax.jit(engine_extend)
        else:
            self._extend = None
        self._next_tok = np.zeros((ecfg.max_batch,), np.int32)

    def set_tracer(self, tracer, replica: int = 0) -> None:
        """Attach an ``obs.tracer`` Tracer; this replica's events carry
        ``replica`` as their Perfetto process id."""
        self.tracer = tracer.for_replica(replica)

    # -- cache backend hooks (overridden by PagedServingEngine) ------------
    def _init_cache(self):
        self.cache = jax.device_put(
            self.entry.cache_zeros(self.ecfg.max_batch, self.ecfg.max_seq,
                                   self.tp), self.device)

    def _claim(self, req: RequestState) -> Optional[int]:
        """Reserve a slot (and, when paged, the prompt's pages; with
        prefix sharing, leading pages already resident are mapped instead
        of allocated)."""
        if not self.free_slots:
            return None
        return self.free_slots.pop()

    def _insert(self, slot: int, new_cache, n_tokens: int) -> None:
        self.cache = _insert_slot(self.cache, new_cache, slot)

    def _release(self, slot: int) -> None:
        self.free_slots.append(slot)

    def _decode_batch(self, toks: jax.Array) -> jax.Array:
        """One decode iteration over all slots; returns logits."""
        logits, self.cache = self._decode(self.params, self.cache, toks)
        return logits

    def _pre_decode_grow(self) -> None:
        """Hook: ensure capacity for the token the step is about to write."""

    def kv_report(self) -> dict:
        """Resident-KV accounting (tokens of cache reserved vs. in use)."""
        used = sum(len(r.prompt) + len(r.tokens_out)
                   for r in self.active.values())
        cap = self.ecfg.max_batch * self.ecfg.max_seq
        return {"mode": "dense", "reserved_tokens": cap,
                "peak_tokens": cap, "used_tokens": used}

    def _budget(self, req: RequestState) -> int:
        """Decode budget: the engine ceiling, tightened by the request's
        trace-sampled early stop (eos-aware traces)."""
        if req.decode_len is not None:
            return min(self.ecfg.max_new_tokens, max(1, req.decode_len))
        return self.ecfg.max_new_tokens

    def _activate(self, slot: int, req: RequestState) -> None:
        """Prefill done, first token emitted: either the request is
        already finished (budget of one, or the first token IS eos) or it
        joins the decode batch."""
        hit_eos = (self.ecfg.eos_id >= 0
                   and req.tokens_out[-1] == self.ecfg.eos_id)
        budget = self._budget(req)
        if hit_eos or len(req.tokens_out) >= budget:
            req.finish_s = time.perf_counter()
            req.finish_reason = (
                "eos" if (hit_eos or budget < self.ecfg.max_new_tokens)
                else "budget")
            self.completed.append(req)
            if self.tracer.enabled:
                self.tracer.emit("finish", ts=req.finish_s, slot=slot,
                                 rid=req.rid, reason=req.finish_reason,
                                 tokens=len(req.tokens_out))
            self._release(slot)
            return
        self.active[slot] = req

    # ------------------------------------------------------------------
    def submit(self, req: RequestState) -> bool:
        """Prefill the request into a free slot; False if engine is full."""
        slot = self._claim(req)
        if slot is None:
            return False
        tr = self.tracer
        # whole-prompt prefill is one maximal "chunk"
        with tr.span("prefill_chunk") as sp:
            t0 = time.perf_counter()
            tokens = jnp.asarray(req.prompt[None, :])
            logits, new_cache = self._prefill(self.params, tokens)
            self._insert(slot, new_cache, len(req.prompt))
            # argmax on device; the int() fetch is the only synchronization
            first = jnp.argmax(logits[0, : self.cfg.vocab])
            with sp.wait():
                first = int(first)
            self._next_tok[slot] = first
            req.slot = slot
            req.prefill_done_s = time.perf_counter() - t0
            req.first_token_s = time.perf_counter()
            req.tokens_out.append(first)
            if tr.enabled:
                sp.args.update(slot=slot, rid=req.rid,
                               tokens=len(req.prompt),
                               pos=len(req.prompt), last=True)
        self._activate(slot, req)
        return True

    def step(self) -> int:
        """One decode iteration for all active slots; returns #finished."""
        if not self.active or self.role == "prefill":
            # prefill-tier engines hold finished prompts for harvest
            # (export_slot_pages) instead of decoding them
            return 0
        tr = self.tracer
        with tr.span("decode_step") as sp:
            batch0 = len(self.active)
            self._pre_decode_grow()
            if tr.enabled:
                sp.args.update(self._step_pages())
            toks = jnp.asarray(self._next_tok)
            logits = self._decode_batch(toks)
            # argmax on device, one host fetch per step — dispatch stays
            # async until the sampled ids are actually needed
            nxt = jnp.argmax(logits[:, : self.cfg.vocab], axis=-1)
            with sp.wait():
                nxt = np.asarray(nxt, np.int32)
            now = time.perf_counter()
            finished = 0
            for slot, req in list(self.active.items()):
                tok = int(nxt[slot])
                req.tokens_out.append(tok)
                req.token_times.append(now)
                hit_eos = self.ecfg.eos_id >= 0 and tok == self.ecfg.eos_id
                # per-request decode budget: a trace-sampled early stop
                # below the engine ceiling models an eos emission
                # (eos-aware traces)
                budget = self._budget(req)
                if hit_eos or len(req.tokens_out) >= budget:
                    req.finish_s = now
                    req.finish_reason = (
                        "eos" if (hit_eos
                                  or budget < self.ecfg.max_new_tokens)
                        else "budget")
                    self.completed.append(req)
                    if tr.enabled:
                        tr.emit("finish", ts=now, slot=slot, rid=req.rid,
                                reason=req.finish_reason,
                                tokens=len(req.tokens_out))
                    del self.active[slot]
                    self._release(slot)
                    finished += 1
                else:
                    self._next_tok[slot] = tok
            if tr.enabled:
                sp.args.update(batch=batch0, finished=finished)
        return finished

    def _step_pages(self) -> dict:
        """Hook (tracing only): the paged decode attention's grid and live
        pages for the step about to run ({}: the dense engine has no
        pages)."""
        return {}

    # -- Sarathi chunk scheduler ---------------------------------------
    def _chunkable(self) -> bool:
        return (self._extend is not None
                and self.ecfg.prefill_chunk is not None)

    def _start_chunked(self, req: RequestState) -> bool:
        """Claim a slot and set up incremental prefill state for ``req``."""
        if self._prefilling is not None:
            return False
        slot = self._claim(req)
        if slot is None:
            return False
        buf = self.entry.cache_zeros(1, self.ecfg.max_seq, self.tp)
        self._prefilling = {"req": req, "slot": slot, "buf": buf,
                            "pos": 0, "t0": time.perf_counter(),
                            "logits": None}
        return True

    def _prefill_chunk_tick(self) -> bool:
        """Advance the in-flight prefill by ONE chunk.  True when the
        request became active (prefill complete)."""
        st = self._prefilling
        if st is None:
            return False
        tr = self.tracer
        req, chunk, slot = st["req"], self.ecfg.prefill_chunk, st["slot"]
        n = len(req.prompt)
        with tr.span("prefill_chunk") as sp:
            take = min(chunk, n - st["pos"])
            toks = jnp.asarray(req.prompt[None, st["pos"]: st["pos"] + take])
            logits, st["buf"] = self._extend(self.params, toks, st["buf"])
            # no sync: chunks chain on device; only the final chunk's
            # argmax (below) fetches a value at the prefill boundary
            st["pos"] += take
            st["logits"] = logits
            last = st["pos"] >= n
            if last:
                # prompt fully consumed: move the buffer into the slot
                self._insert(slot, st["buf"], n)
                self._first_token(sp, st)
            if tr.enabled:
                sp.args.update(slot=slot, rid=req.rid, tokens=take,
                               pos=st["pos"], last=last)
        if not last:
            return False
        self._activate(slot, req)
        self._prefilling = None
        return True

    def _first_token(self, sp, st: dict) -> None:
        """The last prefill chunk's argmax, the one fetch of a chunked
        prefill (it blocks inside the span ``sp``), becomes the request's
        first token."""
        req, slot = st["req"], st["slot"]
        first = jnp.argmax(st["logits"][0, : self.cfg.vocab])
        with sp.wait():
            first = int(first)
        self._next_tok[slot] = first
        req.slot = slot
        req.prefill_done_s = time.perf_counter() - st["t0"]
        req.first_token_s = time.perf_counter()
        req.tokens_out.append(first)

    # -- narrow replica interface (driven by Scheduler / Router) -------
    def admit(self, req: RequestState) -> bool:
        """Claim a slot for ``req`` — chunked prefill start when chunking
        is configured, full prefill otherwise.  False when saturated."""
        return (self._start_chunked(req) if self._chunkable()
                else self.submit(req))

    # -- live co-design (TickLatencyModel accounting channel) ----------
    def _init_codesign(self) -> None:
        self._tick_model = None
        self.modeled_time_s = 0.0
        self._tick_util_sum = 0.0
        self._tick_steps = 0
        if not self.ecfg.codesign:
            return
        from repro.core.hw import fixed_sa_system
        from repro.core.placement import default_system
        from repro.core.serving_sim import nmp_tick_model
        hw = default_system()
        if self.ecfg.codesign_rows:
            sa = hw.substrate
            pes = sa.phys_rows * sa.phys_cols
            hw = fixed_sa_system(self.ecfg.codesign_rows,
                                 pes // self.ecfg.codesign_rows)
        self._codesign_hw = hw
        spec = self.ecfg.codesign_spec or self.entry.config.nmp_spec()
        self._tick_model = nmp_tick_model(
            hw, spec, tp=self.ecfg.codesign_tp or self.tp,
            reconfig_cost_s=self.ecfg.codesign_reconfig_cost_s)

    def _note_tick(self, batch: int, ctxs: List[int], pf_tokens: int,
                   pf_ctx: int) -> None:
        """Price this tick's actual composition on the modeled substrate."""
        if self._tick_model is None or not (batch or pf_tokens):
            return
        prev = (self._tick_model._last_shapes.get(0)
                if self.tracer.enabled else None)
        d = self._tick_model.step(batch, ctxs, prefill_tokens=pf_tokens,
                                  prefill_ctx=pf_ctx)
        self.modeled_time_s += d.time_s + d.reconfig_s
        self._tick_util_sum += d.util
        self._tick_steps += 1
        if prev is not None and prev != d.shapes:
            # instantaneous on the wall clock; the modeled charge rides
            # in args (the sims charge dur on their own clock instead)
            self.tracer.emit("reconfigure", old=str(prev),
                             new=str(d.shapes),
                             modeled_reconfig_s=d.reconfig_s)

    def codesign_report(self) -> dict:
        """Substrate decisions accumulated over the run ({} when off)."""
        if self._tick_model is None:
            return {}
        tm = self._tick_model
        return {"substrate": self._codesign_hw.name,
                "modeled_time_s": self.modeled_time_s,
                "reconfigurations": tm.reconfigurations,
                "substrate_configs": len(tm.configs_seen),
                "array_util_mean": (self._tick_util_sum / self._tick_steps
                                    if self._tick_steps else 0.0)}

    def tick(self) -> int:
        """Advance one iteration: at most one prefill chunk co-scheduled
        with one decode step.  Returns #finished requests."""
        pf_tokens = pf_ctx = 0
        if self._chunkable():
            st = self._prefilling
            if st is not None and self._tick_model is not None:
                pf_tokens = min(self.ecfg.prefill_chunk,
                                len(st["req"].prompt) - st["pos"])
                pf_ctx = st["pos"] + pf_tokens
            self._prefill_chunk_tick()
        if self._tick_model is not None:
            # composition of the decode step about to run (the chunk just
            # ticked may have activated its request into this batch; a
            # prefill-tier engine never decodes, so its batch is empty)
            ctxs = ([len(r.prompt) + len(r.tokens_out)
                     for r in self.active.values()]
                    if self.role != "prefill" else [])
            self._note_tick(len(ctxs), ctxs, pf_tokens, pf_ctx)
        n_fin = self.step()
        if self.tracer.enabled:
            self._trace_gauges()
        return n_fin

    def _trace_gauges(self) -> None:
        """One ``gauge`` event per tick (tracing only): each args key
        becomes a Perfetto counter track."""
        args = {"active": len(self.active),
                "free_slots": len(self.free_slots)}
        if self._tick_model is not None and self.modeled_time_s > 0:
            toks = (sum(len(r.tokens_out) for r in self.completed)
                    + sum(len(r.tokens_out) for r in self.active.values()))
            args["modeled_tokens_per_s"] = toks / self.modeled_time_s
        self.tracer.emit("gauge", **args)

    def busy(self) -> bool:
        return bool(self.active) or self._prefilling is not None

    def load_report(self) -> LoadReport:
        """Load snapshot for front-end routing decisions: resident work
        (``queue_depth``) and headroom (``free_slots`` / ``free_pages``),
        typed per the :mod:`~repro.serving.replica_api` contract."""
        prefilling = int(self._prefilling is not None)
        free = len(self.free_slots)
        # dense engines have no page pool; slots are the capacity
        return LoadReport(
            active=len(self.active), prefilling=prefilling,
            queue_depth=(len(self.active) + prefilling
                         + len(self.requeue)),
            free_slots=free, free_pages=free, min_region_free=free)

    def prefix_residency(self, prompt: np.ndarray) -> int:
        """Prompt pages already resident on this replica (0: none — the
        dense engine shares nothing)."""
        return 0

    # -- disaggregation hooks (PR 10; paged engine overrides) ----------
    def export_slot_pages(self, rid: int) -> Optional[PageShipment]:
        raise RuntimeError("page shipping requires the paged engine "
                           "(EngineConfig.paged)")

    def import_slot_pages(self, shipment: PageShipment) -> bool:
        raise RuntimeError("page shipping requires the paged engine "
                           "(EngineConfig.paged)")

    # -- single-replica driver wrappers --------------------------------
    def run_trace(self, reqs: List[RequestState]) -> dict:
        """Drive an explicit request trace through a single-replica
        :class:`~repro.serving.scheduler.Scheduler` (the loop extracted
        from this class in PR 3)."""
        return Scheduler(self).run_trace(reqs)

    def run_workload(self, *, rate_req_s: float, n_requests: int,
                     prompt_len: int, seed: int = 0,
                     prompt_lens: Optional[np.ndarray] = None,
                     **trace_kwargs) -> dict:
        """Poisson arrivals, wall-clock continuous batching; returns metrics.

        ``prompt_lens`` overrides the constant ``prompt_len`` per request
        (skewed-length traces); remaining ``trace_kwargs`` (``eos_rate``,
        ``sessions``) are threaded through to :func:`make_trace` instead
        of being silently dropped."""
        reqs = make_trace(self.cfg.vocab, rate_req_s=rate_req_s,
                          n_requests=n_requests, prompt_len=prompt_len,
                          seed=seed, prompt_lens=prompt_lens,
                          **trace_kwargs)
        return self.run_trace(reqs)


# ---------------------------------------------------------------------------
# Paged engine
# ---------------------------------------------------------------------------
class PagedServingEngine(ServingEngine):
    """Block-table KV residency + on-demand page growth + preemption."""

    def _init_cache(self):
        ecfg = self.ecfg
        if ecfg.page_size <= 0:
            raise ValueError(f"page_size must be positive, "
                             f"got {ecfg.page_size}")
        max_blocks = num_blocks(ecfg.max_seq, ecfg.page_size)
        n_pages = ecfg.num_pages or ecfg.max_batch * max_blocks
        if n_pages < max_blocks:
            raise ValueError(
                f"num_pages={n_pages} cannot hold even one max-length "
                f"context ({max_blocks} pages)")
        pmap = None
        self._hw = None
        if ecfg.placement is not None:
            from repro.core.placement import PlacementMap, default_system
            self._hw = default_system()
            pmap = PlacementMap.from_system(
                self._hw, n_pages,
                communal_frac=(ecfg.communal_frac
                               if ecfg.prefix_sharing else 0.0),
                n_regions=ecfg.placement_regions)
        self.paged = PagedCache(self.entry, max_batch=ecfg.max_batch,
                                max_seq=ecfg.max_seq,
                                page_size=ecfg.page_size,
                                num_pages=n_pages, tp=self.tp,
                                share=ecfg.prefix_sharing,
                                placement=pmap,
                                placement_policy=(ecfg.placement
                                                  or "free-first"),
                                device=self.device)
        # PagedCache rounds max_seq up to a whole number of pages; adopt
        # the rounded value so prefill buffers, gather views and occupancy
        # math all agree with the table capacity (kv_report asserts this)
        ecfg.max_seq = self.paged.max_seq
        self._lengths_host = np.zeros((ecfg.max_batch,), np.int64)
        self.pages_peak = 0
        self.pages_logical_peak = 0
        self.dedup_ratio_peak = 1.0
        self.defrag_runs = 0
        # prompt tokens whose extend_step compute was skipped because the
        # shared-prefix trie already held their KV (chunked prefill)
        self.prefill_tokens_skipped = 0
        self._gather_cost_sum = 0.0
        self._gather_conc_sum = 0.0
        self._gather_cost_steps = 0
        # per-iteration gather-cost samples (obs histogram source; one
        # float per decode iteration under a placement map)
        self.gather_cost_samples: List[float] = []
        self._region_peak: Dict[int, int] = {}
        self._paged_decode = None   # built lazily (pallas path)
        # fused multi-step decode (lax.scan engine core): one jitted
        # callable per bucketed horizon length
        self._fused_jits: Dict[int, Any] = {}
        self._fused_ticks = 0
        self._fused_steps_sum = 0
        # realized horizons (obs histogram source) and the constraint
        # that clamped the most recent one (fused_tick trace events)
        self.fused_horizons: List[int] = []
        self._last_horizon_clamp = "fuse_steps"

    def set_tracer(self, tracer, replica: int = 0) -> None:
        super().set_tracer(tracer, replica)
        self.paged.tracer = self.tracer   # CoW / defrag / migrate events

    # -- capacity ------------------------------------------------------
    def _claim(self, req: RequestState) -> Optional[int]:
        if not self.free_slots:
            return None
        slot = self.free_slots.pop()
        tokens = req.prompt if self.paged.share else None
        if not self.paged.alloc_slot(slot, len(req.prompt) + 1,
                                     tokens=tokens):
            self.free_slots.append(slot)
            return None
        self._note_pages()
        return slot

    def _insert(self, slot: int, new_cache, n_tokens: int) -> None:
        self.paged.write_slot(slot, new_cache, n_tokens)
        self._lengths_host[slot] = n_tokens

    def _release(self, slot: int) -> None:
        self.paged.free_slot(slot)
        self._lengths_host[slot] = 0
        self._maybe_defrag()
        super()._release(slot)

    def _maybe_defrag(self) -> None:
        """Fragmentation-threshold defrag trigger: compact the page pool
        when the live set has drifted too far from the lowest indices (the
        gather's DMA pattern is densest on a compact pool)."""
        thr = self.ecfg.defrag_threshold
        if thr is None or not self.paged.has_seq:
            return
        if self.paged.fragmentation() > thr:
            # defrag also runs the spilled-page home-migration repair
            # pass (placed mode), priced on the engine's hardware model
            self.paged.defrag(self._hw)
            self.defrag_runs += 1

    def _note_pages(self) -> None:
        physical = self.paged.pages_in_use()
        self.pages_peak = max(self.pages_peak, physical)
        logical = self.paged.logical_pages()
        self.pages_logical_peak = max(self.pages_logical_peak, logical)
        if physical:
            self.dedup_ratio_peak = max(self.dedup_ratio_peak,
                                        logical / physical)
        if self.paged.placement is not None:
            for r, u in self.paged.alloc.region_used().items():
                self._region_peak[r] = max(self._region_peak.get(r, 0), u)

    def _note_gather_cost(self) -> None:
        """Score the active slots' block tables against the substrate
        (one sample per decode iteration)."""
        if self.paged.placement is None or not self.active:
            return
        cost, conc = self.paged.gather_cost_mean(
            self._hw, slots=sorted(self.active))
        self._gather_cost_sum += cost
        self._gather_conc_sum += conc
        self._gather_cost_steps += 1
        self.gather_cost_samples.append(cost)

    def load_report(self) -> LoadReport:
        base = super().load_report()
        if not self.paged.has_seq:
            return base
        free_pages = self.paged.alloc.free_pages
        region_free: tuple = ()
        min_region_free = free_pages
        if self.paged.placement is not None:
            # per-region pressure: the scarcest slot region is what
            # gates an affinity admission staying fully co-located
            free = self.paged.alloc.region_free()
            slot_free = tuple(free[r] for r in free if r >= 0)
            region_free = slot_free
            min_region_free = min(slot_free)
        return LoadReport(
            active=base.active, prefilling=base.prefilling,
            queue_depth=base.queue_depth, free_slots=base.free_slots,
            free_pages=free_pages, min_region_free=min_region_free,
            region_free=region_free)

    def prefix_residency(self, prompt: np.ndarray) -> int:
        return self.paged.prefix_residency(prompt)

    # -- prefill/decode disaggregation (PR 10) -------------------------
    def export_slot_pages(self, rid: int) -> Optional[PageShipment]:
        """Package request ``rid``'s finished-prefill slot for shipment
        to a decode-tier replica, and release the slot here.

        Returns ``None`` while the request is still mid chunked-prefill
        (handoff is deferred — the harvester retries next tick).  The
        shipment carries the request object, its first decoded token
        (produced at the prefill boundary on THIS replica, so the
        decode tier continues the exact greedy stream), and the priced
        cross-stack movement cost.
        """
        st = self._prefilling
        if st is not None and st["req"].rid == rid:
            return None                 # mid-prefill: defer the handoff
        slot = next((s for s, r in self.active.items() if r.rid == rid),
                    None)
        if slot is None:
            raise KeyError(f"request {rid} is not resident")
        req = self.active.pop(slot)
        shipment = self.paged.export_slot_pages(
            slot, int(self._lengths_host[slot]), tokens=req.prompt,
            sys=self._hw, hops=1)
        shipment.req = req
        shipment.next_tok = int(self._next_tok[slot])
        self._lengths_host[slot] = 0
        self._maybe_defrag()
        self.free_slots.append(slot)
        return shipment

    def import_slot_pages(self, shipment: PageShipment) -> bool:
        """Splice a prefill-tier shipment into a free slot and join the
        request to this replica's decode batch.  ``False`` when no slot
        or insufficient pages are available (caller re-targets/retries);
        atomic either way."""
        if not self.free_slots:
            return False
        slot = self.free_slots.pop()
        if not self.paged.import_slot_pages(slot, shipment):
            self.free_slots.append(slot)
            return False
        req = shipment.req
        self._lengths_host[slot] = shipment.n_tokens
        self._next_tok[slot] = shipment.next_tok
        req.slot = slot
        self.active[slot] = req
        self._note_pages()
        return True

    # -- chunked prefill straight into block-table pages ---------------
    def _start_chunked(self, req: RequestState) -> bool:
        if not self.paged.has_seq:
            # recurrent families: state is slot-dense, keep the buffer path
            return super()._start_chunked(req)
        if self._prefilling is not None:
            return False
        slot = self._claim(req)     # reserves prompt pages, maps shared ones
        if slot is None:
            return False
        # Shared-prefix compute skip: pages mapped from the trie already
        # hold this prompt's leading KV, so extension starts at the
        # shared-page boundary instead of recomputing resident chunks
        # (their writes were being routed to the scratch page anyway —
        # pure wasted compute).  At least the final prompt token is kept
        # so the last chunk's logits still yield the first output token,
        # which also covers the exact-tail case where the *whole* prompt
        # is resident.
        n = len(req.prompt)
        resident = min(int(self.paged.shared_count[slot])
                       * self.ecfg.page_size, n)
        start = min(resident, n - 1)
        self.prefill_tokens_skipped += start
        self._prefilling = {"req": req, "slot": slot, "pos": start,
                            "t0": time.perf_counter(), "logits": None,
                            "direct": True}
        return True

    def _prefill_chunk_tick(self) -> bool:
        """Advance the in-flight prefill by ONE chunk, writing it directly
        into the slot's pages (gather window -> extend_step -> scatter
        chunk) — no dense staging buffer, no admission-time copy."""
        st = self._prefilling
        if st is None or not st.get("direct"):
            return super()._prefill_chunk_tick()
        tr = self.tracer
        req, chunk, slot = st["req"], self.ecfg.prefill_chunk, st["slot"]
        n = len(req.prompt)
        with tr.span("prefill_chunk") as sp:
            take = min(chunk, n - st["pos"])
            toks = jnp.asarray(req.prompt[None, st["pos"]: st["pos"] + take])
            view = self.paged.gather_slot(slot, st["pos"])
            logits, view = self._extend(self.params, toks, view)
            # no sync: the scatter chains on the extend on device; only
            # the final chunk's argmax (below) fetches a value
            self.paged.scatter_chunk(slot, view, st["pos"], take)
            st["pos"] += take
            st["logits"] = logits
            last = st["pos"] >= n
            if last:
                # prompt fully consumed: publish prefix pages, activate
                # the slot
                self.paged.commit_prefix(slot)
                self._lengths_host[slot] = n
                self._note_pages()
                self._first_token(sp, st)
            if tr.enabled:
                sp.args.update(slot=slot, rid=req.rid, tokens=take,
                               pos=st["pos"], last=last)
        if not last:
            return False
        self._activate(slot, req)
        self._prefilling = None
        return True

    def kv_report(self) -> dict:
        # _init_cache reconciled the engine's max_seq with the paged
        # cache's page-rounded window; occupancy math is wrong if the two
        # (or the table capacity) ever drift apart again
        assert (self.paged.max_seq == self.ecfg.max_seq
                == self.paged.max_blocks * self.ecfg.page_size), \
            "engine max_seq out of sync with page-table capacity"
        used = sum(len(r.prompt) + len(r.tokens_out)
                   for r in self.active.values())
        rep = {"mode": "paged",
               "reserved_tokens": self.paged.kv_tokens_resident(),
               "peak_tokens": self.pages_peak * self.ecfg.page_size,
               "used_tokens": used,
               "logical_peak_pages": self.pages_logical_peak,
               "dedup_ratio_peak": self.dedup_ratio_peak,
               "defrag_runs": self.defrag_runs,
               "prefill_skipped_tokens": self.prefill_tokens_skipped,
               "migrated_pages": self.paged.migrated_pages,
               "migration_cost_s": self.paged.migration_cost_s,
               "shipped_pages": self.paged.shipped_pages,
               "ship_cost_s": self.paged.ship_cost_s}
        rep.update(self.paged.sharing_report())
        if self.paged.placement is not None:
            steps = max(1, self._gather_cost_steps)
            rep.update(self.paged.placement_report().to_dict())
            rep["region_peak"] = {str(r): u
                                  for r, u in self._region_peak.items()}
            rep["gather_cost_mean_s"] = self._gather_cost_sum / steps
            rep["gather_concentration_mean"] = (
                self._gather_conc_sum / steps
                if self._gather_cost_steps else 1.0)
        return rep

    # -- decode --------------------------------------------------------
    def _pre_decode_grow(self) -> None:
        """Grow every active slot to cover the token this step writes;
        preempt the youngest request when the pool runs dry."""
        tr = self.tracer
        pages0 = (self.paged.pages_in_use()
                  if tr.enabled and self.paged.has_seq else 0)
        for slot in sorted(self.active):
            if slot not in self.active:      # preempted mid-loop
                continue
            need = num_blocks(int(self._lengths_host[slot]) + 1,
                              self.ecfg.page_size)
            if need > self.paged.max_blocks:
                # preemption can never fix a max_seq overflow — don't
                # evict innocents on the way to an inevitable failure
                raise RuntimeError(
                    f"slot {slot} context {self._lengths_host[slot] + 1} "
                    f"exceeds max_seq={self.paged.max_seq}")
            while not self.paged.extend_slot(
                    slot, int(self._lengths_host[slot]) + 1):
                victim = self._pick_victim(exclude=slot)
                if victim is None:
                    raise RuntimeError(
                        "page pool exhausted with no preemptible request")
                self._preempt(victim)
            if self.paged.share:
                # the write may target a shared page (identical-prompt
                # tail): fork it now so the jitted scatter / Pallas kernel
                # only ever writes exclusively-owned pages
                while not self.paged.cow_for_write(
                        slot, int(self._lengths_host[slot])):
                    victim = self._pick_victim(exclude=slot)
                    if victim is None:
                        raise RuntimeError(
                            "page pool exhausted with no preemptible "
                            "request (copy-on-write fork)")
                    self._preempt(victim)
        self._note_pages()
        if tr.enabled and self.paged.has_seq:
            grown = self.paged.pages_in_use() - pages0
            if grown > 0:
                tr.emit("grow", pages=grown)

    def _pick_victim(self, exclude: int) -> Optional[int]:
        cands = [s for s in self.active if s != exclude]
        if not cands:
            return None
        # youngest request (latest arrival) loses its pages
        return max(cands, key=lambda s: (self.active[s].arrival_s,
                                         self.active[s].rid))

    def _preempt(self, slot: int) -> None:
        req = self.active.pop(slot)
        if self.tracer.enabled:
            self.tracer.emit("preempt", slot=slot, rid=req.rid,
                             preemptions=req.preemptions + 1)
        self._release(slot)
        req.reset_generation()
        req.preemptions += 1
        self.preemption_count += 1
        self.requeue.append(req)

    def _decode_batch(self, toks: jax.Array) -> jax.Array:
        ecfg = self.ecfg
        self._note_gather_cost()
        lengths_pre = self._lengths_host.copy()
        active = np.zeros((ecfg.max_batch,), bool)
        for s in self.active:
            active[s] = True
        if (ecfg.use_pallas_decode and self.paged.has_seq
                and self.cfg.family in _ATTN_FAMILIES):
            logits = self._decode_paged_pallas(toks, active)
        else:
            cache = self.paged.gather()
            logits, new_cache = self._decode(self.params, cache, toks)
            self.paged.scatter_token(new_cache, lengths_pre, active)
        self._lengths_host[active] += 1
        return logits

    def _decode_paged_pallas(self, toks: jax.Array,
                             active: np.ndarray) -> jax.Array:
        """Block-table read-through decode: no dense gather materialized."""
        from repro.kernels import ops as kops
        mod, cfg, tp = self.entry.module, self.cfg, self.tp
        if self._paged_decode is None:
            def engine_paged_decode(params, tokens, kp, vp, tables,
                                    lengths):
                return mod.decode_step_paged(
                    params, cfg, tokens, kp, vp, tables, lengths, tp=tp,
                    attn_fn=kops.attention_decode_paged)

            self._paged_decode = jax.jit(engine_paged_decode,
                                         donate_argnums=(2, 3))
        seq_idx = [i for i, s in enumerate(self.paged.is_seq) if s]
        assert len(seq_idx) == 2, "pallas paged decode expects k/v pools"
        ki, vi = seq_idx
        store = list(self.paged.store)
        lengths = jnp.asarray(
            np.where(active, self._lengths_host, 0), jnp.int32)
        # a lane outside the decode batch can still have pages mapped (a
        # slot mid chunked-prefill — with sharing, possibly live *shared*
        # prefix pages): the kernel writes each lane's K/V unconditionally,
        # so route every inactive lane's window to the scratch page.  The
        # table itself comes from the incrementally maintained device
        # mirror — no per-tick numpy rebuild/upload — and the masking runs
        # on device over that mirror
        t = jnp.where(jnp.asarray(active)[:, None],
                      self.paged.tables_device(), self.paged.num_pages)
        logits, (kp, vp, new_len) = self._paged_decode(
            self.params, toks, store[ki], store[vi], t, lengths)
        store[ki], store[vi] = kp, vp
        # the lengths leaf is the only rank-1 non-seq leaf the step advances
        li = [i for i, s in enumerate(self.paged.is_seq)
              if not s and store[i].ndim == 1]
        assert len(li) == 1
        store[li[0]] = jnp.where(jnp.asarray(active), new_len,
                                 store[li[0]])
        self.paged.store = store
        return logits

    # -- fused multi-step decode (device-resident lax.scan core) -------
    def tick(self) -> int:
        if (self.ecfg.fuse_steps <= 1 or not self.paged.has_seq
                or self.cfg.family not in _ATTN_FAMILIES
                or self.role == "prefill"
                or not hasattr(self.entry.module, "decode_fused_paged")):
            return super().tick()
        return self._fused_tick()

    def _fused_horizon(self) -> int:
        """K = min(fuse_steps, steps until any active slot crosses its
        mapped page window, min remaining decode budget) — computed from
        ``_lengths_host`` so nothing inside the scan ever needs a page
        allocation, and budget finishes land exactly on the final step.
        Token-level eos cannot be predicted from host state; those lanes
        freeze on device instead (``emitted`` masks their tail steps)."""
        ps = self.ecfg.page_size
        k = self.ecfg.fuse_steps
        clamp = "fuse_steps"            # which constraint set the horizon
        for slot, req in self.active.items():
            cap = (len(self.paged.blocks_of(slot)) * ps
                   - int(self._lengths_host[slot]))
            if cap < k:
                k, clamp = cap, "page_edge"
            bud = self._budget(req) - len(req.tokens_out)
            if bud < k:
                k, clamp = bud, "budget"
        self._last_horizon_clamp = clamp
        return max(1, k)

    def _cow_horizon(self, k: int) -> None:
        """Fork every shared page the next ``k`` writes can touch.
        Shared pages are immutable while their refcount is > 1, so
        forking at the horizon boundary is content-identical to forking
        at the write step — only the fork's *timing* moves, never the
        copied bytes.  Preempts on fork-allocation failure, exactly like
        the per-step CoW pass."""
        if not self.paged.share:
            return
        ps = self.ecfg.page_size
        for slot in sorted(self.active):
            if slot not in self.active:      # preempted mid-loop
                continue
            ln = int(self._lengths_host[slot])
            for blk in range(ln // ps, (ln + k - 1) // ps + 1):
                while not self.paged.cow_for_write(slot, blk * ps):
                    victim = self._pick_victim(exclude=slot)
                    if victim is None:
                        raise RuntimeError(
                            "page pool exhausted with no preemptible "
                            "request (fused copy-on-write fork)")
                    self._preempt(victim)
        self._note_pages()

    def _fused_fn(self, n_steps: int):
        """Jitted K-step scan, cached per bucketed horizon length."""
        fn = self._fused_jits.get(n_steps)
        if fn is None:
            mod, cfg, tp = self.entry.module, self.cfg, self.tp
            attn_fn = None
            if self.ecfg.use_pallas_decode:
                from repro.kernels import ops as kops
                attn_fn = kops.attention_decode_paged
            eos = self.ecfg.eos_id

            def engine_fused_decode(params, toks, kp, vp, tables, lengths,
                                    alive, ka):
                return mod.decode_fused_paged(
                    params, cfg, toks, kp, vp, tables, lengths, alive, ka,
                    n_steps, tp=tp, attn_fn=attn_fn, eos_id=eos)

            # one XLA module per bucketed horizon, named by it
            engine_fused_decode.__name__ = f"engine_fused_decode_{n_steps}"
            fn = jax.jit(engine_fused_decode, donate_argnums=(2, 3))
            self._fused_jits[n_steps] = fn
        return fn

    def _fused_tick(self) -> int:
        """One scheduler tick = one prefill chunk + a K-step fused scan.

        The host surfaces only here, at the fusion-horizon boundary:
        admission/chunk advance, page growth + preemption, CoW forks,
        then ONE device dispatch and ONE fetch for all K tokens, then
        finish bookkeeping.  Falls back to the per-step path when the
        horizon degenerates to a single step."""
        ecfg = self.ecfg
        tr = self.tracer
        pf_tokens = pf_ctx = 0
        if self._chunkable():
            st = self._prefilling
            if st is not None and self._tick_model is not None:
                pf_tokens = min(ecfg.prefill_chunk,
                                len(st["req"].prompt) - st["pos"])
                pf_ctx = st["pos"] + pf_tokens
            self._prefill_chunk_tick()
        if not self.active:
            self._note_tick(0, [], pf_tokens, pf_ctx)
            if tr.enabled:
                self._trace_gauges()
            return 0
        # the span starts after the co-scheduled prefill chunk so
        # prefill/decode phases stay disjoint in trace_report
        with tr.span("fused_tick") as sp:
            with sp.phase("prepare"):
                self._pre_decode_grow()
                k = self._fused_horizon()
                if k > 1:
                    inputs = self._fused_inputs(k)
            if k > 1:
                finished = self._fused_run(sp, k, inputs, pf_tokens,
                                           pf_ctx)
            else:
                sp.discard()       # the per-step path below has its own
        if k <= 1:
            if self._tick_model is not None:
                ctxs = [len(r.prompt) + len(r.tokens_out)
                        for r in self.active.values()]
                self._note_tick(len(ctxs), ctxs, pf_tokens, pf_ctx)
            finished = self.step()
        if tr.enabled:
            self._trace_gauges()
        return finished

    def _fused_inputs(self, k: int) -> dict:
        """Fork what the next ``k`` writes touch, then upload the fused
        scan's inputs: tokens, lengths, the live-lane mask and the
        masked block tables."""
        ecfg = self.ecfg
        self._cow_horizon(k)
        self._note_gather_cost()     # one placement sample per fused tick
        active = np.zeros((ecfg.max_batch,), bool)
        for s in self.active:
            active[s] = True
        act_dev = jnp.asarray(active)
        # inactive lanes (mid chunked-prefill slots can hold live shared
        # pages) route to the scratch page, on device, over the mirror
        tables = jnp.where(act_dev[:, None], self.paged.tables_device(),
                           self.paged.num_pages)
        return {"base_ctx": {s: len(r.prompt) + len(r.tokens_out)
                             for s, r in self.active.items()},
                "act_dev": act_dev, "tables": tables,
                "lengths": jnp.asarray(np.where(active, self._lengths_host,
                                                0), jnp.int32),
                "toks": jnp.asarray(self._next_tok)}

    def _fused_run(self, sp, k: int, inputs: dict, pf_tokens: int,
                   pf_ctx: int) -> int:
        """Dispatch the fused scan, fetch its tokens, apply them; returns
        #finished.  ``sp`` is the tick's span."""
        tr = self.tracer
        seq_idx = [i for i, s in enumerate(self.paged.is_seq) if s]
        assert len(seq_idx) == 2, "fused decode expects k/v pools"
        ki, vi = seq_idx
        store = list(self.paged.store)
        act_dev = inputs["act_dev"]
        # bucket the scan length to a power of two: a handful of compiled
        # horizons serve every K, and lanes freeze at idx >= k on device
        n_steps = 1 << (k - 1).bit_length()
        with sp.phase("dispatch"):
            fn = self._fused_fn(n_steps)
            t_dev0 = time.perf_counter()
            tok_seq, emit_seq, kp, vp, new_len = fn(
                self.params, inputs["toks"], store[ki], store[vi],
                inputs["tables"], inputs["lengths"], act_dev,
                jnp.asarray(k, jnp.int32))
        with sp.wait():
            tok_h = np.asarray(tok_seq)      # the single per-horizon fetch
            emit_h = np.asarray(emit_seq)
        t_dev1 = time.perf_counter()
        with sp.phase("apply"):
            if tr.enabled:
                # from the lengths before the tick, so ahead of the apply
                sp.args.update(self._grid_counts(self._lengths_host,
                                                 emit_h))
            store[ki], store[vi] = kp, vp
            li = [i for i, s in enumerate(self.paged.is_seq)
                  if not s and store[i].ndim == 1]
            assert len(li) == 1
            store[li[0]] = jnp.where(act_dev, new_len, store[li[0]])
            self.paged.store = store
            finished = self._apply_fused(tok_h, emit_h, k, t_dev0, t_dev1)
            if self._tick_model is not None:
                # post-hoc per-step attribution: step j's batch is the
                # lanes that actually ran it (eos'd lanes drop out
                # mid-horizon, as they would tick-by-tick); the prefill
                # chunk rides step 0
                base_ctx = inputs["base_ctx"]
                for j in range(k):
                    ctxs = [base_ctx[s] + j for s in base_ctx
                            if emit_h[j, s]]
                    self._note_tick(len(ctxs), ctxs,
                                    pf_tokens if j == 0 else 0,
                                    pf_ctx if j == 0 else 0)
            self._fused_ticks += 1
            self._fused_steps_sum += k
            self.fused_horizons.append(k)
        if tr.enabled:
            sp.args.update(batch=len(inputs["base_ctx"]), horizon=k,
                           clamp=self._last_horizon_clamp,
                           finished=finished)
        return finished

    def _step_pages(self) -> dict:
        if not self.paged.has_seq:
            return {}
        active = np.zeros((1, self.ecfg.max_batch), bool)
        active[0, list(self.active)] = True
        return self._grid_counts(self._lengths_host, active)

    def _grid_counts(self, lengths: np.ndarray, emitted: np.ndarray
                     ) -> dict:
        """The paged decode attention's pages over one scan of
        ``len(emitted)`` steps (tracing only), in lane-page units per
        layer per KV head.  ``grid_pages`` is what the kernel walks at
        every step the device ran: for each lane, the table entries of
        the context it attends, rounded up to whole blocks of
        ``pages_per_block`` (a frozen lane attends its frozen context,
        and a lane outside the batch one scratch page).  ``live_pages``
        is the pages that held context, ceil((lengths[b] + j + 1) /
        page_size) for each lane b that emitted at step j."""
        from repro.kernels.flash_decode import pages_per_block
        ps = self.ecfg.page_size
        ki = self.paged.is_seq.index(True)
        pool = self.paged.store[ki]
        nblk = self.paged.tables.shape[1]
        ppb = pages_per_block(pool.shape[1:], pool.dtype, nblk)
        # the kernel sees lengths + 1; lanes outside the batch get 0, and
        # every lane in it runs step 0
        base = np.where(emitted[0], lengths, 0)
        ctx = base + np.cumsum(emitted, axis=0) - emitted + 1
        walk = np.minimum(-(-ctx // ps), nblk)
        live = np.where(emitted, -(-ctx // ps), 0)
        return {"grid_pages": int((-(-walk // ppb) * ppb).sum()),
                "live_pages": int(live.sum())}

    def _trace_gauges(self) -> None:
        args = {"active": len(self.active),
                "free_slots": len(self.free_slots)}
        if self.paged.has_seq:
            args["free_pages"] = self.paged.alloc.free_pages
            if self.paged.placement is not None:
                free = self.paged.alloc.region_free()
                slot_free = [free[r] for r in free if r >= 0]
                if slot_free:
                    args["min_region_free"] = min(slot_free)
        if self._tick_model is not None and self.modeled_time_s > 0:
            toks = (sum(len(r.tokens_out) for r in self.completed)
                    + sum(len(r.tokens_out) for r in self.active.values()))
            args["modeled_tokens_per_s"] = toks / self.modeled_time_s
        self.tracer.emit("gauge", **args)

    def _apply_fused(self, tok_seq: np.ndarray, emit_seq: np.ndarray,
                     k: int, t0: float, t1: float) -> int:
        """Host bookkeeping for one fused tick: append each lane's
        emitted tokens, advance host lengths, retire finished requests.
        ``emit_seq[j, slot]`` masks the steps a lane actually ran —
        a lane frozen by an eos mid-horizon emits nothing afterwards, so
        every append MUST stay behind the emit guard (the mirror-drift
        checker's fused-emit-guard invariant; an unguarded append
        double-counts the finished lane's last token)."""
        ecfg = self.ecfg
        times = [t0 + (j + 1) * (t1 - t0) / k for j in range(k)]
        finished = 0
        for slot, req in list(self.active.items()):
            last_t = t1
            for j in range(k):
                if not emit_seq[j, slot]:
                    continue
                req.tokens_out.append(int(tok_seq[j, slot]))
                req.token_times.append(times[j])
                last_t = times[j]
                self._lengths_host[slot] += 1
            hit_eos = (ecfg.eos_id >= 0
                       and req.tokens_out[-1] == ecfg.eos_id)
            budget = self._budget(req)
            if hit_eos or len(req.tokens_out) >= budget:
                req.finish_s = last_t
                req.finish_reason = (
                    "eos" if (hit_eos or budget < ecfg.max_new_tokens)
                    else "budget")
                self.completed.append(req)
                if self.tracer.enabled:
                    self.tracer.emit("finish", ts=last_t, slot=slot,
                                     rid=req.rid,
                                     reason=req.finish_reason,
                                     tokens=len(req.tokens_out))
                del self.active[slot]
                self._release(slot)
                finished += 1
            else:
                self._next_tok[slot] = req.tokens_out[-1]
        return finished

    def fused_report(self) -> dict:
        """Fused-tick accounting ({} before any fused tick ran)."""
        if not self._fused_ticks:
            return {}
        return {"fused_ticks": self._fused_ticks,
                "fused_steps_mean": (self._fused_steps_sum
                                     / self._fused_ticks)}

    def reset_fused_counters(self) -> None:
        """Zero the fused-tick accounting — warmup-then-measure drivers
        call this between the compile run and the timed run so the
        report covers only the measured region."""
        self._fused_ticks = 0
        self._fused_steps_sum = 0
        self.fused_horizons = []


def make_engine(entry: registry.ArchEntry, ecfg: EngineConfig,
                tp: int = 1, device=None) -> ServingEngine:
    cls = PagedServingEngine if ecfg.paged else ServingEngine
    return cls(entry, ecfg, tp=tp, device=device)
