"""The replica contract (PR 10): one explicit interface for every
"replica" the router can drive.

Three implementations must stay interchangeable behind the
:class:`Replica` protocol:

* :class:`repro.serving.engine.ServingEngine` /
  :class:`~repro.serving.engine.PagedServingEngine` — the real JAX
  engines;
* ``repro.core.serving_sim._Replica`` — the analytical cluster mirror
  (modeled clock, no arrays);
* the stub replicas the router's policy unit tests drive.

Before this module the interface was duck-typed across all three and
could drift silently; now the protocol is written down here, each
implementation declares conformance in its docstring, and the
mirror-drift checker (``analysis/checks/mirror_drift.py::
check_replica_protocol``) fails CI when an implementation stops
defining a protocol method.

Contract
--------
``admit(req) -> bool``
    Try to start ``req`` (prefill immediately or begin its chunked
    prefill).  ``False`` means "no capacity right now" — the caller
    retries later; the replica must not have mutated ``req``.
``tick() -> None``
    Advance one scheduling quantum: at most one prefill chunk plus one
    decode iteration (or one fused horizon).
``busy() -> bool``
    Whether any request is resident (active or mid-prefill).
``load_report() -> LoadReport``
    Dispatch-time load signals, typed (see :class:`LoadReport`).
``requeue``
    List attribute of preempted requests awaiting re-admission; the
    scheduler drains it ahead of fresh arrivals.
``export_slot_pages(rid) -> PageShipment | None``
    Disaggregation (prefill tier): package a finished request's KV
    pages, block-table row, and prefix-trie coverage for shipment.
    ``None`` means the request is not shippable *yet* (still mid
    chunked-prefill) — the caller defers and retries.
``import_slot_pages(shipment) -> bool``
    Disaggregation (decode tier): splice a shipment into the local
    paged pool, reconciling refcounts/regions and re-registering the
    trie coverage.  ``False`` means no capacity — the caller retries
    or picks another target.

Typed reports
-------------
:class:`LoadReport` and :class:`PlacementReport` replace the
dict-shaped payloads.  They are frozen dataclasses shared by the
engine and the sims; ``asdict()``/``to_dict()`` at the JSON/metrics
boundary keeps every reported number and key name unchanged (the field
lists are pinned in ``analysis/checks/mirror_spec.py``).
"""
from __future__ import annotations

from dataclasses import dataclass, field
from typing import (Any, Dict, List, Optional, Protocol, Tuple,
                    runtime_checkable)


@dataclass(frozen=True)
class LoadReport:
    """Dispatch-time load signals a router reads off a replica.

    Field names mirror the legacy dict keys exactly; ``to_dict()`` is
    the JSON/metrics boundary.  ``region_free`` is only populated under
    stack-aware placement (empty tuple otherwise), and
    ``min_region_free`` falls back to ``free_pages`` so unplaced pools
    still expose a scalar pressure signal.
    """

    active: int                 # decoding slots
    prefilling: int             # 0/1: a chunked prefill is resident
    queue_depth: int            # active + prefilling + engine requeue
    free_slots: int
    free_pages: int             # page pool headroom (== free_slots dense)
    min_region_free: int        # tightest slot region (free_pages unplaced)
    region_free: Tuple[int, ...] = ()   # per-slot-region free pages

    def to_dict(self) -> Dict[str, Any]:
        d = {"active": self.active, "prefilling": self.prefilling,
             "queue_depth": self.queue_depth,
             "free_slots": self.free_slots,
             "free_pages": self.free_pages,
             "min_region_free": self.min_region_free}
        if self.region_free:
            d["region_free"] = list(self.region_free)
        return d


@dataclass(frozen=True)
class PlacementReport:
    """Stack-aware placement occupancy (``PagedCache.placement_report``).

    ``region_used`` / ``region_free`` map region id (as a string, the
    legacy JSON key shape) to page counts; ``empty`` mirrors the legacy
    "no placement configured -> {}" contract at the dict boundary.
    """

    placement_policy: str = ""
    n_regions: int = 0
    communal_pages: int = 0
    region_used: Dict[str, int] = field(default_factory=dict)
    region_free: Dict[str, int] = field(default_factory=dict)

    @property
    def empty(self) -> bool:
        return not self.placement_policy

    def to_dict(self) -> Dict[str, Any]:
        if self.empty:
            return {}
        return {"placement_policy": self.placement_policy,
                "n_regions": self.n_regions,
                "communal_pages": self.communal_pages,
                "region_used": dict(self.region_used),
                "region_free": dict(self.region_free)}


#: methods every replica implementation must define — pinned in
#: ``mirror_spec.REPLICA_PROTOCOL_METHODS`` and enforced by the
#: mirror-drift checker across engine / sim / test stubs.
REPLICA_METHODS = ("admit", "tick", "busy", "load_report",
                   "export_slot_pages", "import_slot_pages")


@runtime_checkable
class Replica(Protocol):
    """Structural type for a routable replica (see module docstring)."""

    requeue: List[Any]

    def admit(self, req: Any) -> bool: ...

    def tick(self) -> None: ...

    def busy(self) -> bool: ...

    def load_report(self) -> LoadReport: ...

    def export_slot_pages(self, rid: int) -> Optional[Any]: ...

    def import_slot_pages(self, shipment: Any) -> bool: ...
