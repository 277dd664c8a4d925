"""Roofline analysis from the compiled dry-run artifact (no real hardware).

Three terms per (arch x shape x mesh) cell, all in seconds-per-step on a
TPU v5e chip (197 TFLOP/s bf16, 819 GB/s HBM, ~50 GB/s/link ICI):

  compute    = HLO_FLOPs_per_device / peak_FLOPs
  memory     = HLO_bytes_per_device / HBM_bw
  collective = wire_bytes_per_device / ICI_bw

FLOPs/bytes come from ``compiled.cost_analysis()`` of the SPMD-partitioned
module (already per-device).  Collective bytes are NOT in cost_analysis —
they are parsed from the partitioned HLO text: every all-gather /
all-reduce / reduce-scatter / all-to-all / collective-permute contributes
ring-schedule wire bytes derived from its shape and replica-group size.
"""
from __future__ import annotations

import json
import re
from dataclasses import dataclass, field
from typing import Dict, List, Optional, Tuple

from repro.core.hw import (TPU_V5E_HBM_BW, TPU_V5E_HBM_GB, TPU_V5E_ICI_BW,
                           TPU_V5E_PEAK_FLOPS)

_DTYPE_BYTES = {
    "pred": 1, "s8": 1, "u8": 1, "s16": 2, "u16": 2, "bf16": 2, "f16": 2,
    "s32": 4, "u32": 4, "f32": 4, "s64": 8, "u64": 8, "f64": 8,
}

_COLL_RE = re.compile(
    r"=\s*(?P<lhs>\([^)]*\)|\S+)\s+"
    r"(?P<op>all-reduce|all-gather|reduce-scatter|all-to-all|"
    r"collective-permute)(?:-start)?\(")
_SHAPE_RE = re.compile(r"(?P<dt>pred|bf16|f16|f32|f64|s8|u8|s16|u16|s32|u32|"
                       r"s64|u64)\[(?P<dims>[0-9,]*)\]")
_GROUPS_IOTA_RE = re.compile(r"replica_groups=\[(\d+),(\d+)\]")
_GROUPS_LIST_RE = re.compile(r"replica_groups=\{\{([0-9,]+)\}")


def _shape_bytes(lhs: str) -> int:
    total = 0
    for m in _SHAPE_RE.finditer(lhs):
        dims = m.group("dims")
        n = 1
        if dims:
            for d in dims.split(","):
                n *= int(d)
        total += n * _DTYPE_BYTES[m.group("dt")]
    return total


def _group_size(line: str, default: int) -> int:
    m = _GROUPS_IOTA_RE.search(line)
    if m:
        return int(m.group(2))
    m = _GROUPS_LIST_RE.search(line)
    if m:
        return len(m.group(1).split(","))
    return default


@dataclass
class CollectiveProfile:
    bytes_by_op: Dict[str, int] = field(default_factory=dict)
    wire_bytes: int = 0           # ring-schedule bytes per device
    count: int = 0

    def add(self, op: str, payload: int, wire: int):
        self.bytes_by_op[op] = self.bytes_by_op.get(op, 0) + payload
        self.wire_bytes += wire
        self.count += 1


def parse_collectives(hlo_text: str, n_devices: int) -> CollectiveProfile:
    prof = CollectiveProfile()
    for line in hlo_text.splitlines():
        m = _COLL_RE.search(line)
        if not m:
            continue
        op = m.group("op")
        out_bytes = _shape_bytes(m.group("lhs"))
        p = max(2, _group_size(line, n_devices))
        if op == "all-reduce":
            wire = int(2 * (p - 1) / p * out_bytes)
        elif op == "all-gather":
            # output is the gathered tensor; each device receives (p-1)/p
            wire = int((p - 1) / p * out_bytes)
        elif op == "reduce-scatter":
            # output is the scattered shard; input = p * output
            wire = int((p - 1) * out_bytes)
        elif op == "all-to-all":
            wire = int((p - 1) / p * out_bytes)
        else:  # collective-permute
            wire = out_bytes
        prof.add(op, out_bytes, wire)
    return prof


@dataclass
class RooflineReport:
    arch: str
    shape: str
    mesh: str
    n_devices: int
    flops_per_device: float
    hbm_bytes_per_device: float
    collective: CollectiveProfile
    memory_stats: Optional[dict] = None
    model_flops: Optional[float] = None   # 6*N*D (dense) / 6*N_active*D

    @property
    def t_compute(self) -> float:
        return self.flops_per_device / TPU_V5E_PEAK_FLOPS

    @property
    def t_memory(self) -> float:
        return self.hbm_bytes_per_device / TPU_V5E_HBM_BW

    @property
    def t_collective(self) -> float:
        return self.collective.wire_bytes / TPU_V5E_ICI_BW

    @property
    def bottleneck(self) -> str:
        terms = {"compute": self.t_compute, "memory": self.t_memory,
                 "collective": self.t_collective}
        return max(terms, key=terms.get)

    @property
    def roofline_fraction(self) -> float:
        """max(term)/sum-ish utilization proxy: dominant term over the sum —
        1.0 means perfectly overlapped single bottleneck."""
        tot = self.t_compute + self.t_memory + self.t_collective
        return max(self.t_compute, self.t_memory, self.t_collective) / tot \
            if tot else 0.0

    @property
    def useful_flops_fraction(self) -> Optional[float]:
        if self.model_flops is None or not self.flops_per_device:
            return None
        return self.model_flops / self.n_devices / self.flops_per_device

    def to_dict(self) -> dict:
        return {
            "arch": self.arch, "shape": self.shape, "mesh": self.mesh,
            "devices": self.n_devices,
            "flops_per_device": self.flops_per_device,
            "hbm_bytes_per_device": self.hbm_bytes_per_device,
            "collective_wire_bytes": self.collective.wire_bytes,
            "collective_by_op": self.collective.bytes_by_op,
            "collective_count": self.collective.count,
            "t_compute_s": self.t_compute,
            "t_memory_s": self.t_memory,
            "t_collective_s": self.t_collective,
            "bottleneck": self.bottleneck,
            "model_flops": self.model_flops,
            "useful_flops_fraction": self.useful_flops_fraction,
            "memory_stats": self.memory_stats,
        }


def analyze(compiled, *, arch: str, shape: str, mesh_name: str,
            n_devices: int, model_flops: Optional[float] = None
            ) -> RooflineReport:
    ca = compiled.cost_analysis() or {}
    flops = float(ca.get("flops", 0.0))
    hbm = float(ca.get("bytes accessed", 0.0))
    prof = parse_collectives(compiled.as_text(), n_devices)
    ms = None
    try:
        m = compiled.memory_analysis()
        ms = {k: int(getattr(m, k)) for k in
              ("argument_size_in_bytes", "output_size_in_bytes",
               "temp_size_in_bytes", "alias_size_in_bytes")}
        ms["total_hbm_bytes"] = (ms["argument_size_in_bytes"]
                                 + ms["temp_size_in_bytes"]
                                 + ms["output_size_in_bytes"]
                                 - ms["alias_size_in_bytes"])
        ms["fits_v5e_16gb"] = ms["total_hbm_bytes"] <= TPU_V5E_HBM_GB * 2**30
    except Exception:
        pass
    return RooflineReport(arch=arch, shape=shape, mesh=mesh_name,
                          n_devices=n_devices, flops_per_device=flops,
                          hbm_bytes_per_device=hbm, collective=prof,
                          memory_stats=ms, model_flops=model_flops)


# ---------------------------------------------------------------------------
# Scan-undercount corrections.
#
# XLA's cost_analysis counts a while-loop (lax.scan / lax.map) body ONCE, not
# times the trip count.  Three loops matter in this codebase:
#   1. the layer scan           -> corrected by L-differential extrapolation
#                                  (compile at L0 and 2*L0 layers, take the
#                                  per-layer slope) — see launch/dryrun.py;
#   2. blocked attention's (q-block x kv-block) loops inside each layer
#                                  -> corrected analytically below;
#   3. the chunked-CE loss scan over sequence chunks (train only)
#                                  -> corrected analytically below.
# wkv6 / RG-LRU associative scans are bandwidth-shaped, contribute <1% of
# FLOPs, and are left uncorrected (documented in EXPERIMENTS.md).
# ---------------------------------------------------------------------------
_Q_BLOCK = 512   # layers.blocked_attention defaults
_KV_BLOCK = 512
_CE_CHUNK = 512


def _attn_layer_flops(b: int, s_q: int, s_kv: int, hq: int, dh: int) -> float:
    """QK + AV flops for one blocked-attention call (full S^2; masking does
    not skip blocks in the reference implementation)."""
    return 4.0 * b * hq * s_q * s_kv * dh


def _attn_layer_kv_bytes(b: int, s_kv: int, hkv: int, dh: int,
                         nq: int) -> float:
    """K+V bytes re-streamed once per q-block beyond the first."""
    return 2.0 * b * s_kv * hkv * dh * 2 * max(0, nq - 1)


def analytic_corrections(cfg, shape, tp: int, n_devices: int) -> dict:
    """Per-DEVICE (flops, bytes) to ADD to the L-extrapolated measured cost.

    Only applies to train/prefill kinds (decode attention is a plain einsum
    and is fully counted).  All totals are divided by the device count —
    attention shards over (batch x heads) and the CE head over the model
    axis, so per-device work is total/devices to first order.
    """
    kind = shape.kind
    out = {"flops": 0.0, "bytes": 0.0}
    if kind not in ("train", "prefill"):
        return out
    b, s = shape.global_batch, shape.seq_len
    hq, hkv = cfg.padded_heads(tp)
    dh = cfg.d_head
    L = cfg.num_layers

    def add_attn(n_layers, s_q, s_kv, b_=None):
        b_ = b_ or b
        nq = -(-s_q // _Q_BLOCK)
        nk = -(-s_kv // _KV_BLOCK)
        fl = _attn_layer_flops(b_, s_q, s_kv, hq, dh)
        out["flops"] += n_layers * fl * (1.0 - 1.0 / (nq * nk))
        out["bytes"] += n_layers * _attn_layer_kv_bytes(b_, s_kv, hkv, dh, nq)

    fam = cfg.family
    if fam in ("dense", "moe", "vlm"):
        add_attn(L, s, s)
    elif fam == "hybrid":
        n_attn = sum(1 for x in cfg.block_pattern if x == "attn")
        periods = L / max(1, len(cfg.block_pattern))
        add_attn(periods * n_attn, s, s)
    elif fam == "audio":
        f = cfg.encoder_frames
        add_attn(cfg.encoder_layers, f, f)      # encoder self
        add_attn(L, s, s)                       # decoder self
        add_attn(L, s, f)                       # decoder cross
    # ssm: no attention loops

    if kind == "train":
        v = cfg.padded_vocab(tp)
        d = cfg.d_model
        nch = max(1, s // _CE_CHUNK)
        ce_flops = 2.0 * b * s * d * v
        out["flops"] += ce_flops * (1.0 - 1.0 / nch)
        # the (d x V) head weight is re-read once per chunk beyond the first
        out["bytes"] += (nch - 1) * d * v * 4.0   # f32 in the loss

    out["flops"] /= n_devices
    out["bytes"] /= n_devices
    return out


def model_flops_for(cfg, shape) -> float:
    """MODEL_FLOPS = 6*N*D (dense) or 6*N_active*D (MoE) for train;
    2*N_active*tokens for inference steps."""
    spec = cfg.nmp_spec()
    n_active = spec.active_params()
    if shape.kind == "train":
        tokens = shape.global_batch * shape.seq_len
        return 6.0 * n_active * tokens
    if shape.kind == "prefill":
        tokens = shape.global_batch * shape.seq_len
        return 2.0 * n_active * tokens
    tokens = shape.global_batch          # one token per request
    return 2.0 * n_active * tokens
