"""jaxpr traversal/slicing helpers shared by the kernel aliasing lint.

Nothing here executes device code: every analysis operates on the jaxpr
produced by ``jax.make_jaxpr`` (abstract tracing) or on the tiny pure
index-map jaxprs embedded in ``pallas_call`` equations.
"""
from __future__ import annotations

import itertools
from typing import Any, Dict, Iterable, Iterator, List, Optional, Set, Tuple

from jax.extend import core as jcore

Literal = jcore.Literal

#: primitives that write through computed indices into an existing operand
SCATTER_PRIMS = frozenset({
    "scatter", "scatter-add", "scatter_add", "scatter-mul", "scatter-min",
    "scatter-max", "dynamic_update_slice",
})

#: comparison primitives that can express a bounds guard
CMP_PRIMS = frozenset({"lt", "le", "gt", "ge"})

#: call primitives wrapping a nested jaxpr; ``jnp.where`` and most other
#: ``jnp`` functions trace to a ``jit`` equation around their body
CALL_PRIMS = frozenset({"jit", "closed_call", "custom_jvp_call"})


def subjaxprs(eqn) -> List[Any]:
    """All jaxprs nested in one equation's params (jit/cond/scan/...)."""
    out = []
    for v in eqn.params.values():
        if hasattr(v, "jaxpr"):            # ClosedJaxpr
            out.append(v.jaxpr)
        elif isinstance(v, jcore.Jaxpr):
            out.append(v)
        elif isinstance(v, (list, tuple)):
            for x in v:
                if hasattr(x, "jaxpr"):
                    out.append(x.jaxpr)
                elif isinstance(x, jcore.Jaxpr):
                    out.append(x)
    return out


def iter_eqns(jaxpr, recursive: bool = True) -> Iterator[Any]:
    """Yield equations, optionally descending into nested jaxprs."""
    for eqn in jaxpr.eqns:
        yield eqn
        if recursive:
            for sub in subjaxprs(eqn):
                yield from iter_eqns(sub, recursive=True)


def prim_names(jaxpr, recursive: bool = True) -> Set[str]:
    return {e.primitive.name for e in iter_eqns(jaxpr, recursive)}


def literal_values(eqn) -> List[Any]:
    """Python values of the equation's literal operands."""
    out = []
    for v in eqn.invars:
        if isinstance(v, Literal):
            try:
                out.append(v.val.item() if hasattr(v.val, "item") else v.val)
            except (ValueError, AttributeError):
                out.append(v.val)
    return out


def eqn_mentions_literal(eqn, value, recursive: bool = True) -> bool:
    """True when the equation (or a nested jaxpr's equation) carries a
    literal operand equal to ``value``."""
    if any(v == value for v in literal_values(eqn)):
        return True
    if recursive:
        for sub in subjaxprs(eqn):
            for e in sub.eqns:
                if eqn_mentions_literal(e, value, recursive=True):
                    return True
    return False


def eqn_is_select(eqn) -> bool:
    """select_n, or a call whose body is a select (jnp.where)."""
    if eqn.primitive.name == "select_n":
        return True
    if eqn.primitive.name in CALL_PRIMS:
        return any(e.primitive.name == "select_n"
                   for sub in subjaxprs(eqn) for e in iter_eqns(sub))
    return False


def eqn_is_compare(eqn) -> bool:
    if eqn.primitive.name in CMP_PRIMS:
        return True
    if eqn.primitive.name in CALL_PRIMS:
        return any(e.primitive.name in CMP_PRIMS
                   for sub in subjaxprs(eqn) for e in iter_eqns(sub))
    return False


def backward_slice(jaxpr, seed_vars) -> Tuple[List[Any], Set[Any]]:
    """Top-level backward slice from ``seed_vars``.

    Returns ``(eqns, sources)`` where ``eqns`` are the top-level equations
    the seeds transitively depend on and ``sources`` the jaxpr invars
    reached.  Nested jaxprs are treated as opaque nodes (their operands at
    the call site keep the slice sound for dependency questions).
    """
    needed = {v for v in seed_vars if not isinstance(v, Literal)}
    sliced: List[Any] = []
    for eqn in reversed(jaxpr.eqns):
        if any(ov in needed for ov in eqn.outvars):
            sliced.append(eqn)
            for iv in eqn.invars:
                if not isinstance(iv, Literal):
                    needed.add(iv)
    sources = {v for v in jaxpr.invars if v in needed}
    return list(reversed(sliced)), sources


def find_scatters(jaxpr, page_axis_size: int, recursive: bool = True):
    """Scatter-family equations whose written operand has a dimension equal
    to ``page_axis_size`` (the pool's page axis, scratch included)."""
    hits = []
    for eqn in iter_eqns(jaxpr, recursive):
        if eqn.primitive.name in SCATTER_PRIMS:
            aval = getattr(eqn.invars[0], "aval", None)
            if aval is not None and page_axis_size in tuple(aval.shape):
                hits.append(eqn)
    return hits


def find_pallas_calls(jaxpr) -> List[Any]:
    return [e for e in iter_eqns(jaxpr) if e.primitive.name == "pallas_call"]


# --- pallas index-map interpretation -----------------------------------
class UnanalyzableIndexMap(Exception):
    pass


def eval_index_map(index_map_jaxpr, grid: Tuple[int, ...],
                   point: Tuple[int, ...]) -> Tuple[Any, ...]:
    """Evaluate a *pure* index map (no state reads) at one grid point.

    The map's invars are ``grid indices + scalar-prefetch refs``; only
    grid-passthrough and literal outputs are interpreted — anything else
    (arithmetic, smem reads) raises :class:`UnanalyzableIndexMap` so the
    caller can apply the table-deref rules instead.
    """
    jx = index_map_jaxpr.jaxpr if hasattr(index_map_jaxpr, "jaxpr") \
        else index_map_jaxpr
    if jx.eqns:
        raise UnanalyzableIndexMap("index map has equations")
    env: Dict[Any, int] = {v: point[i]
                           for i, v in enumerate(jx.invars[:len(grid)])}
    out = []
    for ov in jx.outvars:
        if isinstance(ov, Literal):
            out.append(int(ov.val))
        elif ov in env:
            out.append(env[ov])
        else:
            raise UnanalyzableIndexMap(f"output {ov} not a grid index")
    return tuple(out)


def classify_index_map(index_map_jaxpr, grid_rank: int) -> str:
    """'pure' (grid passthrough), 'table' (smem deref passthrough), or
    'other' (needs manual review)."""
    jx = index_map_jaxpr.jaxpr if hasattr(index_map_jaxpr, "jaxpr") \
        else index_map_jaxpr
    if not jx.eqns:
        return "pure"
    gets = [e for e in jx.eqns if e.primitive.name == "get"]
    if len(gets) == len(jx.eqns) and gets:
        grid_vars = set(jx.invars[:grid_rank])
        for g in gets:
            # indices into the prefetched table must be raw grid indices
            for iv in g.invars[1:]:
                if not isinstance(iv, Literal) and iv not in grid_vars:
                    return "other"
        get_outs = {g.outvars[0] for g in gets}
        for ov in jx.outvars:
            ok = (isinstance(ov, Literal) or ov in grid_vars
                  or ov in get_outs)
            if not ok:
                return "other"
        return "table"
    return "other"


def grid_points(grid: Tuple[int, ...]) -> Iterable[Tuple[int, ...]]:
    return itertools.product(*(range(int(g)) for g in grid))


# --- guarded-store analysis inside kernel jaxprs ------------------------
def unguarded_writes_to(kernel_jaxpr, target_refs) -> List[Any]:
    """Swaps (ref stores) to any of ``target_refs`` that execute
    unconditionally on every grid step — i.e. not under a ``cond``
    (``pl.when``).  Loop bodies (scan/while) count as unconditional:
    they run on every step too.
    """
    hits: List[Any] = []
    targets = set(target_refs)

    def walk(jaxpr, env: Dict[Any, Any], guarded: bool):
        for eqn in jaxpr.eqns:
            name = eqn.primitive.name
            if "swap" in name and eqn.invars:
                root = env.get(eqn.invars[0], eqn.invars[0])
                if root in targets and not guarded:
                    hits.append(eqn)
            if name == "cond":
                operands = eqn.invars[1:]
                for br in eqn.params.get("branches", ()):
                    sub = br.jaxpr if hasattr(br, "jaxpr") else br
                    sub_env = _bind(sub.invars, operands, env)
                    walk(sub, sub_env, True)
            elif name in ("scan", "while") or name in CALL_PRIMS:
                for key in ("jaxpr", "body_jaxpr", "cond_jaxpr"):
                    cj = eqn.params.get(key)
                    if cj is None:
                        continue
                    sub = cj.jaxpr if hasattr(cj, "jaxpr") else cj
                    sub_env = _bind(sub.invars, eqn.invars, env)
                    walk(sub, sub_env, guarded)
            else:
                for sub in subjaxprs(eqn):
                    walk(sub, _bind(sub.invars, eqn.invars, env), guarded)

    def _bind(sub_invars, operands, env):
        out = dict(env)
        # positional best-effort: refs thread through call boundaries in
        # operand order; extra consts shift positions, so match by aval
        # identity first and position second.
        by_pos = list(operands)
        n = min(len(sub_invars), len(by_pos))
        for sv, ov in zip(sub_invars[-n:], by_pos[-n:]):
            if not isinstance(ov, Literal):
                out[sv] = env.get(ov, ov)
        return out

    walk(kernel_jaxpr, {}, False)
    return hits
