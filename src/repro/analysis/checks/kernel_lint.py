"""Pass 1 — kernel aliasing lint.

Traces every Pallas kernel and jitted scatter path to its jaxpr
(abstract eval only, nothing runs) and statically verifies the scratch/
bounds discipline the paged cache depends on:

* **scatter-window-guard** — a write position past the mapped block-table
  window must be *detected* (a comparison against the window length on the
  index dataflow), not silently clipped onto the last live page (the PR-2
  clip-aliasing bug).
* **scatter-scratch-route** — detected out-of-window / inactive lanes must
  be routed to the pool's scratch page (a select whose branch is the
  scratch page index) so no refcount>1 page can be aliased by the write.
* **scatter-active-guard** — the jitted token scatter's destination must
  depend on the ``active`` lane mask (the PR-2 inactive-lane bug wrote
  through stale tables of parked slots).
* **pallas block mappings** — block-table index maps in ``pallas_call``
  grid specs must pass prefetched table values through unmodified (no
  arithmetic that could push a valid page id out of bounds), pure grid
  index maps must stay inside the padded operand, revisited output blocks
  must only be stored under ``pl.when``, and length-prefetching kernels
  must mask invalid positions.
* **host-side guards** (AST) — the engine routes inactive lanes' table
  rows to scratch before invoking the paged Pallas kernel, chunked
  scatter routes shared-prefix blocks to scratch, and the decode step
  resolves copy-on-write *before* any device write.
"""
from __future__ import annotations

import ast
import inspect
import math
from typing import Callable, List, Optional, Sequence

from .common import Finding
from . import jaxpr_utils as JU

PASS = "kernel-aliasing"

# distinctive small-scope dims so guard literals (nblk, scratch page) do
# not collide with unrelated constants in the traced computation
_NBLK = 7
_POOL_PAGES = 13          # scratch page index == 13, page axis size 14


def _loc(fn) -> tuple:
    try:
        target = inspect.unwrap(fn)
        return (inspect.getsourcefile(target),
                inspect.getsourcelines(target)[1])
    except (TypeError, OSError):
        return (None, None)


def _f(invariant: str, message: str, file=None, line=None, detail=None):
    return Finding(PASS, invariant, message, file=file, line=line,
                   detail=detail)


def _has_window_compare(eqns, nblk: int) -> bool:
    """A comparison primitive carrying the window length as *its own*
    literal operand (searched through nested jaxprs eqn-by-eqn, so an
    unrelated jit call that happens to contain both a compare and the
    constant elsewhere does not satisfy the guard)."""
    for e in eqns:
        if e.primitive.name in JU.CMP_PRIMS \
                and nblk in JU.literal_values(e):
            return True
        for sub in JU.subjaxprs(e):
            for se in JU.iter_eqns(sub):
                if se.primitive.name in JU.CMP_PRIMS \
                        and nblk in JU.literal_values(se):
                    return True
    return False


def _routes_to_scratch(eqns, scratch_page: int) -> bool:
    """A select in the slice one of whose branches is the scratch page —
    either as a call-site literal (jnp.where lowers to a jit call taking the
    scalar) or via a one-hop broadcast/convert of the literal."""
    producers = {ov: e for e in eqns for ov in e.outvars}
    for e in eqns:
        if not JU.eqn_is_select(e):
            continue
        cand = [e] + [producers[iv] for iv in e.invars
                      if not isinstance(iv, JU.Literal)
                      and iv in producers]
        if any(JU.eqn_mentions_literal(c, scratch_page) for c in cand):
            return True
    return False


# ----------------------------------------------------------------------
# scatter-path checks (jitted token/chunk scatter, decode_step_paged)
# ----------------------------------------------------------------------
def check_scatter_guards(closed, *, scratch_page: int, nblk: int,
                         active_invar: Optional[int], label: str,
                         file=None, line=None) -> List[Finding]:
    """Verify the guard dataflow of every pool scatter in a traced jaxpr.

    When the scatter sits at the jaxpr's top level the check is a precise
    backward slice from the scatter's index operand; when it is nested in
    a loop (``decode_step_paged`` scatters per layer inside ``fori_loop``
    while the page index is computed once outside) the guard chain is
    checked on the top-level computation feeding the loop: the scratch
    select's predicate must descend from an in-window comparison.
    """
    findings: List[Finding] = []
    jaxpr = closed.jaxpr
    page_axis = scratch_page + 1
    top = JU.find_scatters(jaxpr, page_axis, recursive=False)
    nested = JU.find_scatters(jaxpr, page_axis, recursive=True)
    if not nested:
        return [_f("scatter-missing",
                   f"{label}: traced no write into a {page_axis}-page pool "
                   "(lint target misconfigured?)", file, line)]

    def slice_findings(eqns, sources, where: str) -> List[Finding]:
        out = []
        if not _routes_to_scratch(eqns, scratch_page):
            out.append(_f(
                "scatter-scratch-route",
                f"{label}: {where} has no select routing to the scratch "
                f"page ({scratch_page}) — an out-of-window or inactive "
                "lane would alias a live (possibly shared) page",
                file, line))
        if not _has_window_compare(eqns, nblk):
            out.append(_f(
                "scatter-window-guard",
                f"{label}: {where} never compares the block index against "
                f"the table window ({nblk} blocks) — positions past the "
                "window are clipped onto the last live page instead of "
                "detected (PR-2 clip-aliasing class)",
                file, line))
        if active_invar is not None and sources is not None:
            if jaxpr.invars[active_invar] not in sources:
                out.append(_f(
                    "scatter-active-guard",
                    f"{label}: {where} does not depend on the active-lane "
                    "mask — inactive slots would write through their "
                    "stale block tables (PR-2 inactive-lane class)",
                    file, line))
        return out

    if top:
        for eqn in top:
            if eqn.primitive.name == "dynamic_update_slice":
                seeds = eqn.invars[2:]
            else:
                seeds = [eqn.invars[1]]
            eqns, sources = JU.backward_slice(jaxpr, seeds)
            findings += slice_findings(
                eqns, sources, "the scatter's index dataflow")
        return findings

    # nested scatter: guard chain lives at top level, before the loop.
    selects = [e for e in jaxpr.eqns
               if JU.eqn_is_select(e)
               and _routes_to_scratch([e], scratch_page)]
    if not selects:
        findings.append(_f(
            "scatter-scratch-route",
            f"{label}: no top-level select routes the page index to the "
            f"scratch page ({scratch_page}) before the layer loop",
            file, line))
        # without the select there is no predicate to trace
        eqns = list(jaxpr.eqns)
        findings += [f for f in slice_findings(eqns, None,
                                               "the traced computation")
                     if f.invariant == "scatter-window-guard"]
        return findings
    ok = False
    for sel in selects:
        eqns, _ = JU.backward_slice(jaxpr, list(sel.invars))
        eqns.append(sel)
        if _has_window_compare(eqns, nblk):
            ok = True
    if not ok:
        findings.append(_f(
            "scatter-window-guard",
            f"{label}: the scratch-routing select's predicate does not "
            f"descend from an in-window comparison (< {nblk} blocks)",
            file, line))
    return findings


def lint_scatter_token(fn: Optional[Callable] = None) -> List[Finding]:
    """`paged_cache._scatter_token_jit` (or a fixture reintroducing the
    seed-era clipped variant)."""
    import jax
    import jax.numpy as jnp

    if fn is None:
        from repro.serving import paged_cache as pc
        fn = pc._scatter_token_jit
    raw = inspect.unwrap(fn)
    file, line = _loc(fn)
    L, B, D, ps = 1, 2, 8, 4
    pool = jnp.zeros((L, _POOL_PAGES + 1, ps, D))
    leaf = jnp.zeros((L, B, _NBLK * ps, D))
    tables = jnp.zeros((B, _NBLK), jnp.int32)
    pos = jnp.zeros((B,), jnp.int32)
    active = jnp.zeros((B,), bool)
    closed = jax.make_jaxpr(raw)(pool, leaf, tables, pos, active, ps)
    return check_scatter_guards(
        closed, scratch_page=_POOL_PAGES, nblk=_NBLK, active_invar=4,
        label="paged_cache._scatter_token_jit", file=file, line=line)


def lint_decode_step_paged(fn: Optional[Callable] = None) -> List[Finding]:
    """`transformer.decode_step_paged`: the page index feeding the
    per-layer KV scatters must carry the window guard + scratch route."""
    import jax
    import jax.numpy as jnp
    from repro.models import registry
    from repro.models import transformer as T

    fn = fn or T.decode_step_paged
    file, line = _loc(fn)
    entry = registry.get("yi-6b", reduced=True)
    cfg = entry.config
    params = T.init(jax.random.PRNGKey(0), cfg)
    hq, hkv = cfg.padded_heads(1)
    B, ps = 2, 4
    kp = jnp.zeros((cfg.num_layers, _POOL_PAGES + 1, ps, hkv, cfg.d_head))
    vp = jnp.zeros_like(kp)
    tables = jnp.zeros((B, _NBLK), jnp.int32)
    lengths = jnp.zeros((B,), jnp.int32)
    toks = jnp.zeros((B,), jnp.int32)
    closed = jax.make_jaxpr(
        lambda *a: fn(params, cfg, *a))(toks, kp, vp, tables, lengths)
    return check_scatter_guards(
        closed, scratch_page=_POOL_PAGES, nblk=_NBLK, active_invar=None,
        label="transformer.decode_step_paged", file=file, line=line)


# ----------------------------------------------------------------------
# pallas_call block-mapping / output-aliasing lint
# ----------------------------------------------------------------------
def _block_sizes(block_shape) -> Sequence[int]:
    return [b if isinstance(b, int) else 1 for b in block_shape]


def lint_pallas_jaxpr(closed, label: str, file=None, line=None
                      ) -> List[Finding]:
    findings: List[Finding] = []
    calls = JU.find_pallas_calls(closed.jaxpr)
    if not calls:
        return [_f("pallas-missing",
                   f"{label}: traced no pallas_call", file, line)]
    for eqn in calls:
        gm = eqn.params["grid_mapping"]
        grid = tuple(int(g) for g in gm.grid)
        ni = int(getattr(gm, "num_index_operands", 0))
        nin = int(gm.num_inputs)
        nout = int(gm.num_outputs)
        kj = eqn.params["jaxpr"]
        bms = list(gm.block_mappings)
        in_bms, out_bms = bms[:nin], bms[nin:nin + nout]
        base = int(getattr(gm, "num_dynamic_grid_bounds", 0)) + ni
        in_avals = [v.aval for v in eqn.invars[base:base + nin]]
        out_avals = [v.aval for v in eqn.outvars[:nout]]
        table_shapes = [tuple(v.aval.shape)
                        for v in eqn.invars[base - ni:base]]

        pts = list(JU.grid_points(grid)) if math.prod(grid) <= 65536 else \
            list(JU.grid_points(tuple(2 if g > 1 else 1 for g in grid)))

        def analyze(bm, aval, role, j):
            kind = JU.classify_index_map(bm.index_map_jaxpr, len(grid))
            block = _block_sizes(bm.block_shape)
            visits = {}
            if kind == "pure":
                for pt in pts:
                    try:
                        idx = JU.eval_index_map(bm.index_map_jaxpr, grid, pt)
                    except JU.UnanalyzableIndexMap:
                        kind = "other"
                        break
                    for d, (i, bsz) in enumerate(zip(idx, block)):
                        nblocks = -(-int(aval.shape[d]) // bsz)
                        if not (0 <= i < nblocks):
                            findings.append(_f(
                                "pallas-block-bounds",
                                f"{label}: {role} block mapping {j} maps "
                                f"grid point {pt} to block {idx}, outside "
                                f"the padded operand {tuple(aval.shape)}",
                                file, line))
                            return kind, visits
                    visits[idx] = visits.get(idx, 0) + 1
            if kind == "table":
                if role == "output":
                    findings.append(_f(
                        "pallas-output-table-deref",
                        f"{label}: output block mapping {j} addresses the "
                        "output through prefetched table data — data-"
                        "dependent output aliasing cannot be bounded "
                        "statically", file, line))
                else:
                    imj = bm.index_map_jaxpr
                    jx = imj.jaxpr if hasattr(imj, "jaxpr") else imj
                    grid_vars = list(jx.invars[:len(grid)])
                    for g in (e for e in jx.eqns
                              if e.primitive.name == "get"):
                        for pos_i, iv in enumerate(g.invars[1:]):
                            if isinstance(iv, JU.Literal):
                                continue
                            axis = grid_vars.index(iv)
                            tdim = None
                            for ts in table_shapes:
                                if len(ts) > pos_i:
                                    tdim = ts[pos_i]
                            # conservative: the grid axis indexing the
                            # table must not exceed any prefetched
                            # operand's matching dim
                            if tdim is not None and grid[axis] > tdim:
                                findings.append(_f(
                                    "pallas-table-index-bounds",
                                    f"{label}: {role} block mapping {j} "
                                    f"indexes the prefetched table with "
                                    f"grid axis {axis} (size "
                                    f"{grid[axis]}) past the table dim "
                                    f"({tdim})", file, line))
            elif kind == "other":
                findings.append(_f(
                    "pallas-index-map-opaque",
                    f"{label}: {role} block mapping {j} applies arithmetic "
                    "to a table-derived or non-grid index — a valid page "
                    "id could be pushed out of bounds; pass table values "
                    "through unmodified", file, line))
            return kind, visits

        for j, (bm, aval) in enumerate(zip(in_bms, in_avals)):
            analyze(bm, aval, "input", j)
        for j, (bm, aval) in enumerate(zip(out_bms, out_avals)):
            kind, visits = analyze(bm, aval, "output", j)
            if kind == "pure" and visits and max(visits.values()) > 1:
                out_ref = kj.invars[ni + nin + j]
                bad = JU.unguarded_writes_to(kj, [out_ref])
                if bad:
                    findings.append(_f(
                        "pallas-output-aliasing",
                        f"{label}: output block {j} is revisited by "
                        f"{max(visits.values())} grid steps but stored "
                        "unconditionally — later steps clobber earlier "
                        "ones; guard the store with pl.when on the final "
                        "visit", file, line))
        if ni >= 2:
            prims = JU.prim_names(kj)
            if not ({"lt", "le", "gt", "ge"} & prims
                    and "select_n" in prims):
                findings.append(_f(
                    "pallas-length-mask",
                    f"{label}: kernel prefetches lengths but has no "
                    "compare+select masking — scratch/garbage positions "
                    "would contribute to the softmax", file, line))
    return findings


def lint_flash_decode() -> List[Finding]:
    import jax
    import jax.numpy as jnp
    from repro.kernels import flash_decode as FD

    findings: List[Finding] = []
    B, Hq, Hkv, D, ps = 2, 4, 2, 16, 8
    q = jnp.zeros((B, Hq, D))
    kp = jnp.zeros((_POOL_PAGES + 1, ps, Hkv, D))
    vp = jnp.zeros_like(kp)
    tables = jnp.zeros((B, _NBLK), jnp.int32)
    lengths = jnp.zeros((B,), jnp.int32)
    file, line = _loc(FD.paged_flash_decode)
    closed = jax.make_jaxpr(
        lambda *a: FD.paged_flash_decode(*a))(q, kp, vp, tables, lengths)
    findings += lint_pallas_jaxpr(closed, "flash_decode.paged_flash_decode",
                                  file, line)
    T = 32
    k = jnp.zeros((B, T, Hkv, D))
    v = jnp.zeros((B, T, Hkv, D))
    file, line = _loc(FD.flash_decode)
    closed = jax.make_jaxpr(
        lambda *a: FD.flash_decode(*a))(q, k, v, lengths)
    findings += lint_pallas_jaxpr(closed, "flash_decode.flash_decode",
                                  file, line)
    return findings


def lint_snake_gemm() -> List[Finding]:
    import jax
    import jax.numpy as jnp
    from repro.kernels import snake_gemm as SG

    findings: List[Finding] = []
    m, n, k = 4, 256, 256
    a = jnp.zeros((m, k), jnp.float32)
    b = jnp.zeros((k, n), jnp.float32)
    file, line = _loc(SG.snake_decode_gemm)
    for mp in (SG.GemmMapping("IS", 8, 128, k),
               SG.GemmMapping("OS", 8, 128, 128)):
        closed = jax.make_jaxpr(
            lambda x, y, mp=mp: SG.snake_decode_gemm(x, y, mp))(a, b)
        findings += lint_pallas_jaxpr(
            closed, f"snake_gemm.snake_decode_gemm[{mp.dataflow}]",
            file, line)
    return findings


def lint_wkv6() -> List[Finding]:
    import jax
    import jax.numpy as jnp
    from repro.kernels import wkv6 as W

    b, t, h, hs = 1, 4, 2, 8
    r = jnp.zeros((b, t, h, hs), jnp.float32)
    u = jnp.zeros((h, hs), jnp.float32)
    s0 = jnp.zeros((b, h, hs, hs), jnp.float32)
    file, line = _loc(W.wkv6)
    closed = jax.make_jaxpr(
        lambda *a: W.wkv6(*a))(r, r, r, r, u, s0)
    return lint_pallas_jaxpr(closed, "wkv6.wkv6", file, line)


# ----------------------------------------------------------------------
# host-side guard checks (AST)
# ----------------------------------------------------------------------
def _parse(path: str) -> ast.Module:
    with open(path, "r") as fh:
        return ast.parse(fh.read(), filename=path)


def _find_funcs(tree: ast.Module, name: str) -> List[ast.FunctionDef]:
    return [n for n in ast.walk(tree)
            if isinstance(n, ast.FunctionDef) and n.name == name]


def _has_where_guard(func: ast.FunctionDef, *needles: str) -> bool:
    """A ``*.where(...)`` call whose argument source mentions every
    needle — the host-side scratch-routing idiom."""
    for node in ast.walk(func):
        if (isinstance(node, ast.Call)
                and isinstance(node.func, ast.Attribute)
                and node.func.attr == "where"):
            src = " ".join(ast.unparse(a) for a in node.args)
            if all(n in src for n in needles):
                return True
    return False


def _calls_in_order(func: ast.FunctionDef, first: str, second: str) -> bool:
    """``first(...)`` is invoked at a smaller line than ``second(...)``."""
    lines = {first: None, second: None}
    for node in ast.walk(func):
        if isinstance(node, ast.Call):
            name = (node.func.attr if isinstance(node.func, ast.Attribute)
                    else getattr(node.func, "id", ""))
            if name in lines and lines[name] is None:
                lines[name] = node.lineno
    return (lines[first] is not None and lines[second] is not None
            and lines[first] < lines[second])


def check_inactive_lane_ast(path: Optional[str] = None,
                            func_name: str = "_decode_paged_pallas"
                            ) -> List[Finding]:
    """The engine must route *inactive* lanes' block-table rows to the
    scratch page before handing tables to the Pallas kernel: the kernel
    writes every lane unconditionally, so an inactive lane with mapped
    (possibly shared) pages would be corrupted (PR-2 inactive-lane bug)."""
    if path is None:
        from repro.serving import engine as E
        path = inspect.getsourcefile(E)
    tree = _parse(path)
    funcs = _find_funcs(tree, func_name)
    if not funcs:
        return [_f("host-inactive-lane",
                   f"no function {func_name} found", path)]
    out = []
    for fn in funcs:
        if not _has_where_guard(fn, "active", "num_pages"):
            out.append(_f(
                "host-inactive-lane",
                f"{func_name} never routes inactive lanes to the scratch "
                "page (expected a where(active, ..., num_pages) on the "
                "table rows before the kernel call)",
                path, fn.lineno))
    return out


def check_scatter_chunk_ast(path: Optional[str] = None) -> List[Finding]:
    """`PagedCache.scatter_chunk` must route shared-prefix blocks to the
    scratch page — chunked prefill over a CoW-shared prefix would
    otherwise overwrite pages other slots still read."""
    if path is None:
        from repro.serving import paged_cache as PC
        path = inspect.getsourcefile(PC)
    tree = _parse(path)
    funcs = _find_funcs(tree, "scatter_chunk")
    if not funcs:
        return [_f("host-shared-chunk-route",
                   "no scatter_chunk found", path)]
    out = []
    for fn in funcs:
        if not _has_where_guard(fn, "shared_count", "num_pages"):
            out.append(_f(
                "host-shared-chunk-route",
                "scatter_chunk does not route shared-prefix blocks to "
                "the scratch page (expected where(blk < shared_count, "
                "num_pages, ...))", path, fn.lineno))
    return out


def check_cow_order_ast(path: Optional[str] = None) -> List[Finding]:
    """CoW-before-write: the per-step grow hook must fork shared pages
    (`cow_for_write`) and run *before* the device decode write."""
    if path is None:
        from repro.serving import engine as E
        path = inspect.getsourcefile(E)
    tree = _parse(path)
    out = []
    grows = _find_funcs(tree, "_pre_decode_grow")
    paged_grow = [g for g in grows
                  if any(isinstance(n, ast.Call)
                         and isinstance(n.func, ast.Attribute)
                         and n.func.attr == "cow_for_write"
                         for n in ast.walk(g))]
    if not paged_grow:
        out.append(_f(
            "host-cow-before-write",
            "no _pre_decode_grow variant calls cow_for_write — shared "
            "pages would be written in place", path,
            grows[0].lineno if grows else None))
    steps = [s for s in _find_funcs(tree, "step")
             if _calls_in_order(s, "_pre_decode_grow", "_decode_batch")]
    if not steps:
        out.append(_f(
            "host-cow-before-write",
            "no step() invokes _pre_decode_grow before _decode_batch — "
            "the CoW fork must precede the device write", path))
    return out


# ----------------------------------------------------------------------
def run() -> List[Finding]:
    findings: List[Finding] = []
    findings += lint_scatter_token()
    findings += lint_decode_step_paged()
    findings += lint_flash_decode()
    findings += lint_snake_gemm()
    findings += lint_wkv6()
    findings += check_inactive_lane_ast()
    findings += check_scatter_chunk_ast()
    findings += check_cow_order_ast()
    return findings
