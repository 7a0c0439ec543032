"""Aggregate-serving layer: compiled-plan + slot-table caching with
same-shape request batching.

Aggify turns a cursor loop into ONE pipelined aggregate query — but
production traffic is thousands of *parameterized repeats* of a few such
queries (every dashboard tile, every per-user UDF invocation), and a bare
``engine.execute`` pays three per-call costs the repeats never need:

* **jaxpr retrace + XLA compile** — the plan, catalog shapes, and
  parameter dtypes fully determine the computation; only parameter
  *values* change between calls.  The server keys an executable cache on
  exactly that: plan identity, the catalog shape/dtype signature, the
  parameter signature, the ``bucket_group_bound`` shape bucket, and the
  batch-size bucket — all finite, so the trace count is bounded by the
  number of distinct shape buckets, not the request count.
* **key→slot probing** (``relational/keyslot.py``) — the sort-free
  grouped route re-derives the same hash-slotted segment assignment from
  the same rows on every call.  The server builds it once per
  ``(table version, key columns, bucket)``, validates the dense bound
  *concretely* (overflow raises here, not inside a trace), and provides
  it to the executable as an **argument** via ``keyslot.provide_slots``.
  Passing slots as arguments — never baking them into the trace as
  constants — is what makes stale reads structurally impossible: a
  mutated table carries a fresh ``Table.version``, the slot cache misses,
  and the same compiled executable runs with the rebuilt arrays.  For
  row-sharded tables the cached assignment doubles as the *stable
  cross-call global* slot table the per-shard launcher cannot offer.
* **one-request-at-a-time launches** — concurrent parameterized calls
  with the same plan and parameter signature coalesce into one
  ``jax.vmap`` launch over stacked per-request parameter vectors
  (the grouped-decorrelation trick of ``benchmarks/tpch_loops.py``,
  generalized from benchmark code into the engine): tables and slot
  arrays broadcast, parameters batch.

When a grouped root plan declares no ``max_groups`` and its input table
carries no ``declare_group_bound`` hint, the server infers one: the
linear-counting ``distinct_count_sketch`` estimates the distinct key
count, the estimate is padded and bucketed, and the eager slot build
*validates* it (an overflowing inferred bound doubles and rebuilds —
never trusted, per the validated-not-assumed rule of
relational/group_bound.py).

**Failure semantics** (the guard layer, default on): every failure is a
typed ``serve.guard.ServeError`` set on the request's future — a bound
the data outgrew (``BoundOverflow``), a poisoned launch converted from
silent NaNs to ``PoisonedResult`` (retried with a doubled bound when the
bound was inferred), a deadline shed in the queue
(``DeadlineExceeded``), admission backpressure (``QueueFull``), a
kernel-backend failure the degradation ladder couldn't absorb
(``BackendFailure``).  The dispatcher thread is supervised (respawned on
death) and the per-(plan, signature) circuit breaker trips repeated
backend failures onto the always-correct jnp executable.  See
docs/serving.md, "Failure semantics".

Kill switches: ``REPRO_AGG_SERVE=off`` bypasses every cache and batch —
each call runs a plain eager ``engine.execute``;
``REPRO_SERVE_GUARD=off`` disables the guard layer only (PR-6 serving
behavior: caches and batching, raw exceptions).

**Tracing**: the dispatcher's work is always spanned with
``jax.profiler.TraceAnnotation`` (``agg.batch`` around one launch, with
``agg.prepare``, ``agg.args``, ``agg.dispatch``, ``agg.await``,
``agg.guard_scan``, ``agg.unbatch`` and ``agg.deliver`` inside it, and
``agg.coalesce`` around the batching window), each tagged with the plan's
name and the ids of its requests, so a profiler trace shows the host path
on the device ops' clock.  With no profiler running a span costs about a
microsecond.  See docs/serving.md, "Tracing a live server".

See docs/serving.md for the cache-key / invalidation / batching contract.
"""
from __future__ import annotations

import copy
import dataclasses
import itertools
import logging
import math
import threading
import time
import warnings
from concurrent.futures import Future
from contextlib import contextmanager, nullcontext
from dataclasses import dataclass, field
from dataclasses import replace as _dc_replace
from typing import Any, Dict, Mapping, NamedTuple, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from repro.configs import flags
from repro.launch.sharded_agg import declare_row_split
from repro.relational import keyslot
from repro.relational.engine import execute, grouped_route
from repro.relational.group_bound import GroupBoundOverflow, resolve_group_bound
from repro.relational.keyslot import check_slot_overflow
from repro.relational.plan import AggCall, GroupAgg, Plan, Scan
from repro.relational.table import Table
from repro.reliability import degrade, faults

from . import incremental
from .guard import (BackendFailure, BoundOverflow, CircuitBreaker,
                    DeadlineExceeded, GuardStats, PoisonedResult, QueueFull,
                    ServeError, ServerClosed, SlotTableStale, is_poisoned,
                    strip_poison_stamp)
from .incremental import IncrementalIneligible

__all__ = ["AggServer", "ServeStats", "ServeRequest", "ServeResult",
           "serving_enabled", "guard_enabled"]

_log = logging.getLogger(__name__)


def _plan_name(plan: Plan) -> str:
    """The name a plan's spans carry: the aggregate's name for an
    ``AggCall``, else the plan node's type."""
    agg = getattr(plan, "aggregate", None)
    return getattr(agg, "name", None) or type(plan).__name__


def serving_enabled() -> bool:
    """Kill switch for the whole serving layer (default: on).
    ``REPRO_AGG_SERVE=off`` turns every call into a plain eager
    ``engine.execute`` — no executable cache, no slot-table cache, no
    batching."""
    return flags.enabled("REPRO_AGG_SERVE")


def guard_enabled() -> bool:
    """Default for ``AggServer(guard=...)``: on unless
    ``REPRO_SERVE_GUARD=off``.  Guard-off restores the PR-6 serving
    behavior exactly — caches and batching, raw exceptions on futures,
    no poison scan, no breaker, unbounded queue."""
    return flags.enabled("REPRO_SERVE_GUARD")


#: bounded poison recovery: an inferred bound that poisons a launch is
#: doubled and rebuilt at most this many times before the failure
#: surfaces as ``PoisonedResult``
_MAX_POISON_RETRIES = 2

#: bounded staleness recovery: a slot-table entry whose version tag
#: disagrees with the catalog is dropped and rebuilt at most this many
#: times per launch before ``SlotTableStale`` surfaces
_MAX_STALE_REBUILDS = 2


@dataclass
class ServeStats:
    """Counters the tests and the serving bench assert on.  ``traces``
    increments inside the jitted body (a Python side effect fires only
    while tracing), so it counts actual retraces, not calls.
    ``slot_extends`` counts incremental slot-table extensions (an append
    that reused the resident assignment instead of rebuilding);
    ``folds`` counts resident micro-batch moment folds.
    ``queue_wait_s`` sums, over every request the dispatcher took for a
    launch, the seconds from its ``submit`` to that moment (shed
    requests are not counted).  ``sharded_launches`` counts the launches
    of plans whose grouped node sorts a row-split catalog table shard by
    shard (``GroupRoute.merge``)."""
    requests: int = 0
    batches: int = 0
    traces: int = 0
    slot_builds: int = 0
    slot_hits: int = 0
    slot_extends: int = 0
    appends: int = 0
    ingests: int = 0
    folds: int = 0
    snapshots: int = 0
    epoch_reads: int = 0    # lock-free published-epoch decodes
    checkpoints: int = 0    # durable checkpoints written
    restores: int = 0       # durable checkpoints restored
    queue_wait_s: float = 0.0
    sharded_launches: int = 0


@dataclass(frozen=True)
class ServeRequest:
    """The ONE request shape every serving entry point speaks (the typed
    front door; ``execute``/``submit`` are thin wrappers over it).

    * ``plan``        — the plan to serve (interned by identity);
    * ``params``      — scalar parameter bindings (values vary per call,
                        the signature keys the executable cache);
    * ``deadline``    — seconds from submission after which a QUEUED
                        request is shed with ``DeadlineExceeded``
                        (async path only);
    * ``consistency`` — ``"latest"`` (default): compute over the current
                        catalog tables; ``"snapshot"``: serve a grouped
                        plan from its resident incremental moment state
                        (``AggServer.snapshot`` — O(num_segments)
                        finalize, no history re-read), catching up on
                        pending appends first; ``"epoch"``: decode the
                        resident's currently *published* epoch with NO
                        server lock — never blocks on an in-flight fold
                        or ``update_table``, may trail the newest append
                        by the fold in flight (the result's ``version``
                        is the epoch watermark actually served).  Both
                        fall back to a full compute when the plan is
                        ineligible or ``REPRO_INCR_AGG=off``.
    """
    plan: Plan
    params: Optional[Mapping[str, Any]] = None
    deadline: Optional[float] = None
    consistency: str = "latest"


@dataclass(frozen=True)
class ServeResult:
    """What a ``ServeRequest`` resolves to: the result ``table``, the
    ``version`` of the plan's slot-scan catalog table at launch (None
    when the plan has no slot scan — e.g. joins), and a point-in-time
    copy of the server's ``stats`` counters."""
    table: Table
    version: Optional[int]
    stats: "ServeStats"


#: safety padding on the sketch estimate before bucketing: linear
#: counting is unbiased but noisy (±O(√m) keys), and the power-of-two
#: bucket only forgives undershoot up to the next boundary
_SKETCH_PAD = 1.3
_SKETCH_SLACK = 16


class _Queued(NamedTuple):
    """A request waiting in the admission queue."""
    params: Mapping[str, Any]
    fut: Future
    deadline: Optional[float]   # time.monotonic() past which it is shed
    rid: int                    # the request's id in the spans
    t_submit: float             # time.perf_counter() at submit


@dataclass
class _PlanEntry:
    """Per-plan serving state.  ``plan`` is the plan as served — when the
    bound was inferred it differs from the submitted plan by
    ``max_groups`` only.  Keyed by ``id(submitted plan)``; the entry
    holds a strong reference to the submitted plan so the id stays
    valid."""
    submitted: Plan
    plan: Plan
    keys: Tuple[str, ...] = ()
    bound: Optional[int] = None      # validated bucket; None → no slots
    slot_scan: Optional[str] = None  # catalog table the slots align to
    inferred: bool = False           # bound came from the sketch (growable)
    shard_local: bool = False        # group sort runs shard by shard
    execs: Dict[Any, Any] = field(default_factory=dict)


class AggServer:
    """Serve parameterized aggregate plans over a named catalog.

    ``serve(ServeRequest) -> ServeResult`` is the typed request path;
    ``execute(plan, params)`` is its synchronous positional wrapper
    (cache-aware, one request per launch) and ``submit(plan, params) ->
    Future`` / ``serve_async`` the concurrent path — a dispatcher thread
    coalesces same-(plan, parameter-signature) requests into one vmapped
    launch of up to ``max_batch`` lanes.  Writes go through the typed
    mutation API: ``update_table`` (replace — full invalidation),
    ``append_rows`` (append — executables survive, slot tables extend),
    ``ingest`` (append + fold into resident incremental aggregates;
    ``snapshot(plan)`` finalizes them in O(num_segments)).
    ``execute_uncached`` reproduces the pre-serving cost model (fresh
    jit per call) for benchmarking."""

    def __init__(self, catalog: Mapping[str, Table], *,
                 max_batch: int = 64, batch_window_s: float = 0.001,
                 infer_bounds: bool = True, guard: Optional[bool] = None,
                 max_queue: int = 1024, breaker_threshold: int = 3,
                 breaker_cooldown_s: float = 5.0, breaker_clock=None):
        # a row-sharded table's split is declared on the table itself, so
        # the executables traced over it launch the kernel per shard
        self._catalog: Dict[str, Table] = {
            name: declare_row_split(t) for name, t in catalog.items()}
        self._max_batch = max(1, int(max_batch))
        self._batch_window = float(batch_window_s)
        self._infer_bounds = bool(infer_bounds)
        self._guard = guard_enabled() if guard is None else bool(guard)
        self._max_queue = max(1, int(max_queue))
        self._breaker_threshold = int(breaker_threshold)
        self._breaker_cooldown = float(breaker_cooldown_s)
        self._breaker_clock = breaker_clock or time.monotonic
        self._lock = threading.RLock()
        #: dedicated small mutex for counter mutation and stat/breaker
        #: snapshots — ``describe()`` and ``ServeStats`` reads never
        #: contend with a fold holding ``_lock``.  Lock order where both
        #: are held: ``_lock`` then ``_stats_lock``, never the reverse.
        self._stats_lock = threading.Lock()
        self._cv = threading.Condition()
        self._plans: Dict[int, _PlanEntry] = {}
        #: (table name, table version, key names, bucket) →
        #: (version tag, slot arrays, SlotState | None) — the tag
        #: re-proves the version at every hit (see _slot_table); the
        #: state lets an append EXTEND the assignment instead of
        #: rebuilding it
        self._slots: Dict[Any, tuple] = {}
        #: (table name, new version) → (parent version, appended
        #: positions) — the append chain slot extension and snapshot
        #: catch-up walk; broken by update_table (full invalidation)
        self._appends: Dict[Any, tuple] = {}
        #: id(plan) → ResidentAgg — resident incremental moment state
        #: (the plan entry in _plans holds the strong plan reference)
        self._residents: Dict[int, incremental.ResidentAgg] = {}
        self._pending: Dict[Any, tuple] = {}
        self._breakers: Dict[Any, CircuitBreaker] = {}
        #: resident-state payloads recovered by ``restore`` awaiting a
        #: structurally matching plan: fingerprint → rehydration record
        #: (serve/checkpoint.py); consumed at first ``snapshot``
        self._restored: Dict[str, dict] = {}
        #: synthetic version tokens for rehydrated watermarks — negative
        #: (live ``Table.version`` tokens are positive, so they never
        #: collide), one per rehydration
        self._synth_version = 0
        self._dispatcher: Optional[threading.Thread] = None
        self._closed = False
        self.stats = ServeStats()
        self.guard_stats = GuardStats()
        #: keys whose absorbed backend failure was already logged
        self._logged_failures: set = set()
        #: request ids, one per request in submission order
        self._req_ids = itertools.count(1)
        #: TraceMe metadata (plan, request ids) of the launch this
        #: thread is running, carried by every span inside it
        self._span_tags = threading.local()

    # -- spans -------------------------------------------------------------
    def _span(self, name: str):
        return jax.profiler.TraceAnnotation(
            name, **(getattr(self._span_tags, "v", None) or {}))

    @contextmanager
    def _batch_span(self, plan: Plan, ids):
        """``agg.batch`` around one launch of the requests ``ids``; the
        spans opened inside it on this thread carry the same tags."""
        tags = {"plan": _plan_name(plan), "reqs": " ".join(map(str, ids))}
        self._span_tags.v = tags
        try:
            with jax.profiler.TraceAnnotation("agg.batch", **tags):
                yield
        finally:
            self._span_tags.v = None

    # -- stats plumbing ----------------------------------------------------
    def _bump(self, name: str, k: float = 1) -> None:
        with self._stats_lock:
            setattr(self.stats, name, getattr(self.stats, name) + k)

    def _gbump(self, name: str, k: int = 1) -> None:
        with self._stats_lock:
            setattr(self.guard_stats, name,
                    getattr(self.guard_stats, name) + k)

    def _stats_copy(self) -> ServeStats:
        with self._stats_lock:
            return copy.copy(self.stats)

    def _log_absorbed(self, key, what: str, exc: Exception) -> None:
        """Log a backend failure the jnp rung absorbed, with its
        exception, once per ``key`` — (plan, signature) for launches —
        so a kernel that never runs is visible beyond the counters."""
        with self._stats_lock:
            if key in self._logged_failures:
                return
            self._logged_failures.add(key)
        _log.warning("%s: kernel backend failed; served by the degraded "
                     "jnp path (backend_failures counts every repeat)",
                     what, exc_info=exc)

    # -- catalog writes: the typed mutation API ----------------------------
    #
    # Three verbs with three invalidation contracts (docs/serving.md):
    #
    #   update_table(name, t)  REPLACE — content may change arbitrarily.
    #       Invalidates slot tables for the table, the executables of
    #       every plan scanning it, its resident incremental state, and
    #       breaks its append chain.
    #   append_rows(name, rows)  APPEND — existing rows are immutable.
    #       Bumps the version; executables SURVIVE (shapes unchanged
    #       while rows fit the spare capacity) and slot tables EXTEND
    #       incrementally instead of rebuilding.
    #   ingest(name, batch)  APPEND + FOLD — append_rows plus an O(batch)
    #       fold of the batch's moments into every resident incremental
    #       aggregate registered on the table.

    def update_table(self, name: str, table: Table) -> None:
        """REPLACE a catalog table — the big-hammer verb: arbitrary
        content change, full invalidation (slot tables, the executables
        of every plan scanning ``name``, resident incremental state, the
        append chain).  Use ``append_rows``/``ingest`` for append-shaped
        mutations — they keep the caches warm; an append-shaped call
        here draws a ``DeprecationWarning`` pointing at them."""
        with self._lock:
            self._check_open()
            old = self._catalog.get(name)
            if old is not None and self._append_shaped(old, table):
                warnings.warn(
                    f"update_table({name!r}, ...) received an append-shaped "
                    "table (old rows intact, new rows added).  Migrate to "
                    "append_rows(name, rows) — preserves compiled "
                    "executables and extends the slot table incrementally — "
                    "or ingest(name, batch) to also fold resident "
                    "incremental aggregates.  update_table keeps "
                    "full-replace semantics: executables, slot tables, and "
                    "resident state for this table are all invalidated.",
                    DeprecationWarning, stacklevel=2)
            self._catalog[name] = declare_row_split(table)
            self._invalidate(name)

    def append_rows(self, name: str, rows) -> int:
        """APPEND rows to a catalog table; returns the new
        ``Table.version``.  ``rows`` is a Table (its invalid rows are
        dropped) or a mapping of column → array with exactly the
        table's columns.  Rows land in the first invalid positions of
        the fixed-capacity layout; when the spare capacity runs out the
        table GROWS (capacity at least doubles — this changes column
        shapes, so executables legitimately retrace; appends that fit
        the spare capacity change no shape and reuse every executable).
        The append is recorded on the version chain, so slot tables
        extend incrementally (``keyslot.slot_ids_extend``) and resident
        incremental aggregates catch up at the next snapshot.
        ``group_bound`` hints survive (unlike ``relational.concat``)."""
        with self._lock:
            self._check_open()
            t = self._catalog[name]
            prev_version = t.version
            cols, nb = self._coerce_rows(t, rows)
            if nb == 0:
                return t.version
            mask = (np.ones(t.capacity, bool) if t.valid is None
                    else np.asarray(t.valid))
            holes = np.flatnonzero(~mask)
            if len(holes) < nb:
                t = self._grow_capacity(t, nb - len(holes))
                mask = np.asarray(t.valid)
                holes = np.flatnonzero(~mask)
            pos = np.ascontiguousarray(holes[:nb])
            posj = jnp.asarray(pos, jnp.int32)
            new_cols = {c: a.at[posj].set(
                jnp.asarray(cols[c]).astype(a.dtype))
                for c, a in t.columns.items()}
            new_valid = jnp.asarray(mask).at[posj].set(True)
            t2 = Table(new_cols, new_valid, t.group_bound,
                       row_split=t.row_split)
            self._catalog[name] = t2
            self._appends[(name, t2.version)] = (prev_version, pos)
            self._trim_appends(name)
            self._bump("appends")
            return t2.version

    def ingest(self, name: str, batch) -> int:
        """APPEND + FOLD: ``append_rows`` the micro-batch, then fold its
        moments into every resident incremental aggregate registered on
        ``name`` — O(batch) slotting + aggregation and O(num_segments)
        merges per resident plan, never an O(table) recompute.  Returns
        the new table version.  Under the guard a fold failure follows
        the serving ladder (degraded jnp retry → ``BackendFailure``; an
        overflowing inferred bound doubles and retries →
        ``BoundOverflow`` when declared); a failed fold NEVER corrupts
        the resident state (folds commit atomically), and the append
        itself always lands.  ``REPRO_INCR_AGG=off`` reduces this to
        ``append_rows`` (residents drop; snapshots recompute).
        Raises typed ``ServerClosed`` after ``close()`` — a fold already
        holding the lock when ``close`` lands completes and commits; it
        is never torn down mid-commit."""
        with self._lock:
            self._check_open()
            before = self._catalog[name].version
            version = self.append_rows(name, batch)
            self._bump("ingests")
            if not incremental.incremental_enabled() \
                    or not serving_enabled():
                for pid, res in list(self._residents.items()):
                    if res.name == name:
                        del self._residents[pid]
                return version
            if version != before:
                self._fold_residents(name)
            return version

    def table(self, name: str) -> Table:
        with self._lock:
            return self._catalog[name]

    # -- mutation plumbing -------------------------------------------------
    def _check_open(self) -> None:
        """Typed refusal for mutation verbs racing ``close()``: a verb
        that acquired the server lock before the close commits in full
        (fold-and-commit is atomic under the lock); one that arrives
        after loses with ``ServerClosed``, never a half-commit."""
        if self._closed:
            raise ServerClosed("AggServer is closed")

    def _invalidate(self, name: str) -> None:
        """Full invalidation for a REPLACE write on ``name``."""
        self._slots = {k: v for k, v in self._slots.items()
                       if k[0] != name}
        self._appends = {k: v for k, v in self._appends.items()
                         if k[0] != name}
        for pid, res in list(self._residents.items()):
            if res.name == name:
                del self._residents[pid]
        for ent in self._plans.values():
            if name in self._plan_tables(ent.submitted):
                ent.execs.clear()

    @staticmethod
    def _plan_tables(plan: Plan) -> set:
        """Catalog table names a plan tree scans."""
        names, stack = set(), [plan]
        while stack:
            p = stack.pop()
            if isinstance(p, Scan):
                names.add(p.table)
                continue
            if dataclasses.is_dataclass(p):
                for f in dataclasses.fields(p):
                    v = getattr(p, f.name, None)
                    if isinstance(v, Plan):
                        stack.append(v)
        return names

    @staticmethod
    def _append_shaped(old: Table, new: Table) -> bool:
        """Heuristic behind the update_table deprecation warning: True
        when ``new`` is ``old`` with rows added — same columns/dtypes,
        old rows bit-identical in the prefix, old validity preserved,
        and at least one row actually appended."""
        if set(old.columns) != set(new.columns):
            return False
        if new.capacity < old.capacity:
            return False
        oc = old.capacity
        om = np.asarray(old.mask())
        nm = np.asarray(new.mask())
        if not bool((om <= nm[:oc]).all()):      # no row was invalidated
            return False
        for c, a in old.columns.items():
            b = new.columns[c]
            if isinstance(a, jax.core.Tracer) or isinstance(b, jax.core.Tracer):
                return False
            if b.dtype != a.dtype:
                return False
            # appends may fill previously-invalid holes, so only the
            # VALID old rows must survive bit-identically
            if not np.array_equal(np.asarray(a)[om],
                                  np.asarray(b)[:oc][om]):
                return False
        return int(nm.sum()) > int(om.sum())     # and rows were added

    @staticmethod
    def _coerce_rows(t: Table, rows) -> tuple:
        """Normalize an append payload to (column → np array, row count);
        a Table payload drops its invalid rows first."""
        if isinstance(rows, Table):
            keep = np.flatnonzero(np.asarray(rows.mask()))
            cols = {c: np.asarray(a)[keep] for c, a in rows.columns.items()}
        else:
            cols = {c: np.asarray(a) for c, a in dict(rows).items()}
        if set(cols) != set(t.columns):
            raise ValueError(
                f"append columns {sorted(cols)} do not match table "
                f"columns {sorted(t.columns)}")
        lens = {a.shape[0] for a in cols.values()}
        if len(lens) > 1:
            raise ValueError(f"append columns disagree on length: {lens}")
        return cols, (lens.pop() if lens else 0)

    @staticmethod
    def _grow_capacity(t: Table, need: int) -> Table:
        """Grow a table's fixed capacity by at least ``need`` spare rows
        (geometric: at least doubles), padding columns with zeros and the
        validity mask with False.  Shape change ⇒ executables keyed on
        the catalog signature legitimately miss."""
        extra = max(int(need), t.capacity)
        cols = {c: jnp.concatenate(
            [a, jnp.zeros((extra,) + a.shape[1:], a.dtype)])
            for c, a in t.columns.items()}
        valid = jnp.concatenate([t.mask(), jnp.zeros(extra, bool)])
        return Table(cols, valid, t.group_bound, row_split=t.row_split)

    _MAX_APPEND_CHAIN = 64

    def _trim_appends(self, name: str) -> None:
        ours = [k for k in self._appends if k[0] == name]
        for k in ours[:-self._MAX_APPEND_CHAIN]:
            del self._appends[k]

    def _chain_positions(self, name: str, from_version: int,
                         to_version: int):
        """Appended positions between two versions of ``name`` (oldest
        first, concatenated), or None when the chain is broken (an
        update_table happened, or the chain was trimmed)."""
        pend, v = [], to_version
        while v != from_version:
            got = self._appends.get((name, v))
            if got is None:
                return None
            v, pos = got
            pend.append(pos)
        if not pend:
            return np.zeros(0, np.int64)
        return np.concatenate(pend[::-1])

    # -- introspection -----------------------------------------------------
    def describe(self, plan: Plan) -> dict:
        """Serving decisions for a plan (tests/bench introspection).
        Lock-free for an already-prepared plan: the entry lookup and the
        counter/breaker snapshot take only the small stats mutex, so a
        long fold or ``update_table`` holding the server lock never
        blocks this read.  An unprepared plan pays one locked
        ``_prepare`` (its first ``serve`` would have paid it anyway)."""
        ent = self._plans.get(id(plan))
        if ent is None:
            with self._lock:
                ent = self._prepare(plan)
        with self._stats_lock:
            breakers = {psig: br.state
                        for (pid, psig), br in self._breakers.items()
                        if pid == id(ent.submitted)}
        return {
            "max_groups": getattr(ent.plan, "max_groups", None),
            "bound": ent.bound,
            "slot_scan": ent.slot_scan,
            "inferred": ent.inferred,
            "executables": len(ent.execs),
            "guard": self._guard,
            "breakers": breakers,
        }

    # -- the typed request path --------------------------------------------
    def serve(self, request: ServeRequest) -> ServeResult:
        """Synchronous service of one ``ServeRequest`` — the primary
        entry point (``execute`` is the thin positional wrapper).
        ``consistency="latest"`` computes over the current catalog;
        ``consistency="snapshot"`` finalizes the plan's resident
        incremental moment state (``snapshot``) — parameterized plans
        and ineligible plans fall back to a latest compute.  Deadlines
        apply to QUEUED requests only, i.e. to ``serve_async``."""
        self._check_consistency(request)
        if request.consistency in ("snapshot", "epoch") \
                and not request.params:
            table, version = self._snapshot_versioned(
                request.plan, request.consistency)
            return ServeResult(table=table, version=version,
                               stats=self._stats_copy())
        table = self._execute(request.plan, request.params)
        return self._result(request, table)

    def serve_async(self, request: ServeRequest) -> Future:
        """``serve`` through the batching dispatcher: returns a Future
        resolving to a ``ServeResult`` (or a typed ``ServeError`` under
        the guard — ``request.deadline`` seconds from now sheds the
        request with ``DeadlineExceeded`` while queued).  Snapshot-
        consistency requests resolve inline (the resident finalize is
        O(num_segments) — there is nothing to batch)."""
        self._check_consistency(request)
        if request.consistency in ("snapshot", "epoch") \
                and not request.params:
            fut: Future = Future()
            try:
                fut.set_result(self.serve(request))
            except Exception as e:      # noqa: BLE001 — future carries it
                fut.set_exception(e)
            return fut
        inner = self.submit(request.plan, request.params,
                            deadline=request.deadline)
        out: Future = Future()

        def _done(f: Future) -> None:
            e = f.exception()
            if e is not None:
                out.set_exception(e)
                return
            try:
                out.set_result(self._result(request, f.result()))
            except Exception as ex:     # noqa: BLE001 — future carries it
                out.set_exception(ex)

        inner.add_done_callback(_done)
        return out

    @staticmethod
    def _check_consistency(request: ServeRequest) -> None:
        if request.consistency not in ("latest", "snapshot", "epoch"):
            raise ValueError(
                f"unknown consistency {request.consistency!r} "
                "(expected 'latest', 'snapshot' or 'epoch')")

    def _live_version(self, plan: Plan) -> Optional[int]:
        """The plan's slot-scan catalog version (None when the plan has
        no slot scan).  Lock-free: dict reads are atomic and the result
        is advisory (a concurrent writer may already have moved on)."""
        ent = self._plans.get(id(plan))
        name = ent.slot_scan if ent is not None else None
        t = self._catalog.get(name) if name is not None else None
        return t.version if t is not None else None

    def _result(self, request: ServeRequest, table: Table) -> ServeResult:
        return ServeResult(table=table,
                           version=self._live_version(request.plan),
                           stats=self._stats_copy())

    # -- synchronous path (back-compat wrapper) ----------------------------
    def execute(self, plan: Plan, params: Optional[Mapping[str, Any]] = None
                ) -> Table:
        """Cache-aware execution of one parameterized request — the
        positional wrapper over ``serve(ServeRequest(plan, params))``.
        Serialized under the server lock (deterministic trace
        accounting); use ``submit``/``serve_async`` for concurrency."""
        return self.serve(ServeRequest(plan=plan, params=params)).table

    def _execute(self, plan: Plan,
                 params: Optional[Mapping[str, Any]] = None) -> Table:
        params = dict(params or {})
        if not serving_enabled():
            return execute(plan, self._catalog, params)
        with self._batch_span(plan, [next(self._req_ids)]):
            with self._lock:
                with self._span("agg.prepare"):
                    ent = self._prepare(plan)
                return self._launch(ent, self._psig(params), [params])[0]

    # -- resident incremental aggregation ----------------------------------
    def snapshot(self, plan: Plan) -> Table:
        """Finalize the resident incremental aggregate for ``plan`` —
        O(num_segments) decode of the resident (C, R, S) moment tensor,
        never an O(table) re-read.  First call seeds the residency (one
        full pass); later calls catch up on any ``append_rows`` the
        table took since the last fold (via the version chain) and
        finalize.  An up-to-date residency serves LOCK-FREE from its
        published epoch — a long fold or ``update_table`` in another
        thread never blocks it.  Ineligible plans (non-GroupAgg roots,
        unfused ops, no dense bound, ``REPRO_INCR_AGG=off``) fall back
        to a plain cached compute — same result, full cost."""
        return self._snapshot_versioned(plan, "snapshot")[0]

    def _snapshot_versioned(self, plan: Plan, consistency: str
                            ) -> Tuple[Table, Optional[int]]:
        """(result table, served watermark version).

        Fast path — NO server lock: capture the resident's published
        epoch (one atomic reference read; the epoch is one immutable
        object, so the decode can never see a torn mix of pre-/post-fold
        state).  ``"snapshot"`` takes it only when the epoch is at the
        live catalog version; ``"epoch"`` takes whatever epoch is
        published (pre-fold or post-fold — the returned version says
        which), so it never waits on a fold in flight.

        Slow path — under the lock: seed/rehydrate the residency or
        fold the pending append-chain suffix, then decode."""
        if not serving_enabled() or not incremental.incremental_enabled():
            return self._execute(plan), self._live_version(plan)
        self._bump("snapshots")
        res = self._residents.get(id(plan))
        if res is not None:
            ep = res.current_epoch()
            if ep is not None:
                live = self._catalog.get(res.name)
                fresh = live is not None and ep.version == live.version
                if fresh or consistency == "epoch":
                    self._bump("epoch_reads")
                    out = res.snapshot_epoch(ep, live if fresh else None)
                    if self._guard and is_poisoned(out):
                        raise PoisonedResult(
                            "resident snapshot carries the poison stamp")
                    return strip_poison_stamp(out), ep.version
        with self._lock:
            ent = self._prepare(plan)
            res = self._residents.get(id(plan))
            if res is None:
                res = self._rehydrate_resident(ent)
                if res is None:
                    res = self._admit_resident(ent)
                if res is None:
                    out = self._launch(ent, self._psig({}), [{}])[0]
                    return out, self._live_version(plan)
                self._residents[id(plan)] = res
            t = self._catalog[res.name]
            if res.version != t.version:
                pos = self._chain_positions(res.name, res.version,
                                            t.version)
                try:
                    if pos is None:     # chain broken: re-seed
                        self._seed_resident(res)
                    elif len(pos):
                        self._guarded_fold(res, t, pos)
                        self._bump("folds")
                    else:
                        res.version = t.version
                except IncrementalIneligible:
                    del self._residents[id(plan)]
                    out = self._launch(ent, self._psig({}), [{}])[0]
                    return out, self._live_version(plan)
            out = res.snapshot(self._catalog[res.name])
            version = res.version
        if self._guard and is_poisoned(out):
            raise PoisonedResult(
                "resident snapshot carries the poison stamp")
        return strip_poison_stamp(out), version

    def _rehydrate_resident(self, ent: _PlanEntry):
        """A residency recovered from a durable checkpoint for a
        structurally matching plan, or None (serve/checkpoint.py);
        consumes the stored payload on success.  The recovered epoch
        sits at the checkpoint watermark — the normal version-chain
        catch-up right after folds the append suffix through the
        existing fold path."""
        if not self._restored:
            return None
        from . import checkpoint
        return checkpoint.rehydrate(self, ent)

    def _admit_resident(self, ent: _PlanEntry):
        """Admit + seed a residency for a prepared plan entry, or None
        when the plan cannot be served incrementally."""
        if ent.slot_scan is None or ent.bound is None:
            return None
        plan = ent.plan
        if not isinstance(plan, GroupAgg):
            return None
        t = self._catalog[ent.slot_scan]
        res = incremental.ResidentAgg.admit(plan, ent.slot_scan, ent.keys,
                                            t, ent.bound)
        if res is None:
            return None
        res.inferred = ent.inferred
        try:
            self._seed_resident(res)
        except IncrementalIneligible:
            return None
        return res

    def _seed_resident(self, res) -> None:
        """Seed (or re-seed) a residency, doubling an overflowing
        inferred bound like the slot-table build does."""
        t = self._catalog[res.name]
        while True:
            try:
                res.seed(t)
                return
            except GroupBoundOverflow:
                if not res.inferred:
                    raise
                _, bound = resolve_group_bound(res.bound * 2, t.capacity)
                if bound is None or bound <= res.bound:
                    raise IncrementalIneligible(
                        "inferred bound outgrew the row capacity")
                res.bound = bound

    def _fold_residents(self, name: str) -> None:
        """Fold the just-appended batch into every resident aggregate on
        ``name`` (the ingest path; each resident catches up through the
        version chain so a resident that missed earlier plain appends
        still converges)."""
        t = self._catalog[name]
        for pid, res in list(self._residents.items()):
            if res.name != name or res.version == t.version:
                continue
            pos = self._chain_positions(name, res.version, t.version)
            try:
                if pos is None:
                    self._seed_resident(res)
                elif len(pos):
                    self._guarded_fold(res, t, pos)
                    self._bump("folds")
                else:
                    res.version = t.version
            except IncrementalIneligible:
                del self._residents[pid]

    def _guarded_fold(self, res, t: Table, pos) -> None:
        """One resident fold under the serving failure contract: the
        ``ingest_fold`` fault site fires first (chaos battery); a
        backend exception retries the fold on the jnp path (degraded);
        an overflowing batch doubles an inferred bucket via
        ``ResidentAgg.grow`` and retries — a declared bound surfaces
        ``BoundOverflow`` (guard) / ``GroupBoundOverflow`` (raw).  Folds
        commit atomically, so every failure leaves the resident state
        untouched."""
        while True:
            try:
                faults.fail("ingest_fold")
                res.fold(t, pos)
                return
            except GroupBoundOverflow as e:
                if res.inferred and res.grow(t):
                    continue
                if not res.inferred:
                    # declared bound: residency cannot absorb the growth
                    self._residents.pop(
                        next((pid for pid, r in self._residents.items()
                              if r is res), None), None)
                    if self._guard:
                        raise BoundOverflow(str(e)) from e
                    raise
                raise IncrementalIneligible(
                    "resident bucket outgrew the row capacity") from e
            except (IncrementalIneligible, ServeError):
                raise
            except Exception as e:      # noqa: BLE001 — ladder absorbs
                if not self._guard:
                    raise
                self._gbump("backend_failures")
                self._log_absorbed((id(res.plan), "ingest_fold"),
                                   "ingest fold", e)
                try:
                    res.fold(t, pos, backend="jnp")
                    self._gbump("degraded_launches")
                    return
                except Exception as e2:  # noqa: BLE001
                    raise BackendFailure(
                        "incremental fold failed and the degraded (jnp) "
                        "fold failed too") from e2

    # -- durable checkpoints -----------------------------------------------
    def checkpoint(self, directory: str) -> Optional[str]:
        """Write a durable checkpoint of every resident incremental
        aggregate (its published epoch: moments, slot table, owner,
        payloads, watermark) to ``directory`` — a versioned, checksummed
        manifest plus one payload file, written temp-then-rename so a
        crash mid-write never leaves a file a later ``restore`` could
        mistake for complete.  Returns the manifest path, or None when
        there is nothing resident to persist or the kill switch
        (``REPRO_SERVE_CKPT=off``) / the serving layer is off."""
        if not flags.enabled("REPRO_SERVE_CKPT") or not serving_enabled():
            return None
        from . import checkpoint as _ckpt
        with self._lock:
            path = _ckpt.write_checkpoint(self, directory)
        if path is not None:
            self._bump("checkpoints")
        return path

    def restore(self, directory: str) -> int:
        """Load the newest checkpoint in ``directory`` and stage its
        resident payloads for rehydration; returns the number staged (0
        when the directory holds no checkpoint or the kill switch is
        off).  Verification is strict: a manifest or payload that fails
        its checksum raises typed ``CheckpointCorrupt`` and installs
        NOTHING — the server keeps serving from live state (recompute),
        never from partially-read durable state.  A staged payload is
        consumed at the first ``snapshot`` of a structurally matching
        plan; any rows appended past the checkpoint watermark replay
        through the normal fold path (the version chain)."""
        if not flags.enabled("REPRO_SERVE_CKPT") or not serving_enabled():
            return 0
        from . import checkpoint as _ckpt
        with self._lock:
            n = _ckpt.read_checkpoint(self, directory)
        if n:
            self._bump("restores")
        return n

    def warmup(self, plan: Plan,
               params: Optional[Mapping[str, Any]] = None,
               batch_sizes: Tuple[int, ...] = (1,)) -> None:
        """Pre-trace the executables for a plan at the given batch-size
        buckets (deploy-time warming: the request path then never pays a
        compile).  ``params`` is a representative parameter dict — only
        its signature matters."""
        params = dict(params or {})
        if not serving_enabled():
            return
        with self._lock:
            ent = self._prepare(plan)
            psig = self._psig(params)
            for nb in batch_sizes:
                self._launch(ent, psig, [params] * max(1, int(nb)))

    def execute_uncached(self, plan: Plan,
                         params: Optional[Mapping[str, Any]] = None
                         ) -> Table:
        """The pre-serving cost model, for comparison: a fresh ``jax.jit``
        closure per call, so every call retraces, recompiles, and
        re-derives its slot table inside the trace."""
        params = dict(params or {})
        env = {k: jnp.asarray(v) for k, v in params.items()}
        with self._lock:
            catalog = dict(self._catalog)
        fn = jax.jit(lambda tabs, e: execute(plan, tabs, e))
        return fn(catalog, env)

    # -- concurrent path ---------------------------------------------------
    def submit(self, plan: Plan,
               params: Optional[Mapping[str, Any]] = None, *,
               deadline: Optional[float] = None) -> Future:
        """Enqueue one parameterized request; the dispatcher coalesces
        same-shape requests into one vmapped launch.  Returns a Future
        resolving to the request's result Table — or, under the guard, to
        a typed ``ServeError``: ``deadline`` (seconds from now) makes the
        dispatcher shed the request with ``DeadlineExceeded`` if it is
        still queued when the deadline passes, and a full admission queue
        rejects immediately with ``QueueFull`` (backpressure, never
        unbounded buffering)."""
        params = dict(params or {})
        fut: Future = Future()
        if not serving_enabled():
            try:
                fut.set_result(execute(plan, self._catalog, params))
            except Exception as e:          # noqa: BLE001 — future carries it
                fut.set_exception(e)
            return fut
        key = (id(plan), self._psig(params))
        dl = None if deadline is None else time.monotonic() + float(deadline)
        rid, t_sub = next(self._req_ids), time.perf_counter()
        with self._cv:
            if self._closed:
                raise ServerClosed("AggServer is closed")
            if self._guard:
                depth = sum(len(r) for _, r in self._pending.values())
                if depth >= self._max_queue:
                    self._gbump("queue_rejects")
                    fut.set_exception(QueueFull(
                        f"admission queue at capacity ({self._max_queue} "
                        f"requests) — retry with backoff or raise max_queue"))
                    return fut
            if self._dispatcher is None:
                self._dispatcher = threading.Thread(
                    target=self._dispatch_main, name="agg-serve-dispatch",
                    daemon=True)
                self._dispatcher.start()
            if key not in self._pending:
                self._pending[key] = (plan, [])
            self._pending[key][1].append(_Queued(params, fut, dl, rid, t_sub))
            self._cv.notify()
        return fut

    def close(self, drain: bool = True) -> None:
        """Stop the dispatcher.  ``drain=True`` (default) lets every
        queued request run to completion first — submits racing the close
        still resolve, new submits after it raise ``ServerClosed``.
        ``drain=False`` fails the queue immediately: every queued
        future gets ``ServerClosed``."""
        with self._cv:
            self._closed = True
            if not drain:
                for _plan, reqs in self._pending.values():
                    for r in reqs:
                        if not r.fut.done():
                            r.fut.set_exception(ServerClosed(
                                "AggServer closed without draining"))
                self._pending.clear()
            self._cv.notify_all()
        # the dispatcher may be respawned by the supervisor mid-close, so
        # join whatever thread currently holds the role until none does
        while True:
            with self._cv:
                th = self._dispatcher
            if th is None or not th.is_alive():
                break
            th.join(timeout=0.1)
        with self._cv:
            self._dispatcher = None

    def __enter__(self) -> "AggServer":
        return self

    def __exit__(self, *exc) -> None:
        self.close()

    def _dispatch_main(self) -> None:
        """Dispatcher supervisor: a dying dispatch loop (a bug, or the
        ``dispatcher_die`` fault) respawns a fresh thread instead of
        stranding every queued future unresolved forever.  Queued
        requests live in ``_pending`` (not thread state), so they
        survive the death and the successor serves them."""
        try:
            self._dispatch_loop()
        except BaseException:   # noqa: BLE001 — supervised: respawn
            with self._cv:
                self._gbump("dispatcher_restarts")
                t = threading.Thread(
                    target=self._dispatch_main, name="agg-serve-dispatch",
                    daemon=True)
                self._dispatcher = t
                t.start()

    def _dispatch_loop(self) -> None:
        while True:
            with self._cv:
                while not self._pending and not self._closed:
                    self._cv.wait()
                if self._closed and not self._pending:
                    return
            if faults.fire("dispatcher_stall"):
                time.sleep(0.25)     # deterministic queue-delay injection
            faults.fail("dispatcher_die")
            if self._batch_window > 0:
                with self._span("agg.coalesce"):
                    time.sleep(self._batch_window)  # let requests coalesce
            while True:
                with self._cv:
                    if not self._pending:
                        break
                    key = next(iter(self._pending))
                    plan, reqs = self._pending[key]
                    take = reqs[:self._max_batch]
                    del reqs[:len(take)]
                    if not reqs:
                        del self._pending[key]
                take = self._shed_expired(take)
                if take:
                    now = time.perf_counter()
                    self._bump("queue_wait_s",
                               sum(now - r.t_submit for r in take))
                    self._run_batch(plan, key[1], take)

    def _shed_expired(self, reqs):
        """Drop queued requests whose deadline already passed — their
        futures fail with ``DeadlineExceeded`` and the launch they would
        have joined never pays for them."""
        now = time.monotonic()
        live = []
        for r in reqs:
            if r.deadline is not None and now > r.deadline:
                self._gbump("deadline_shed")
                if not r.fut.done():
                    r.fut.set_exception(DeadlineExceeded(
                        "request deadline passed while queued"))
            else:
                live.append(r)
        return live

    def _run_batch(self, plan: Plan, psig, reqs) -> None:
        with self._batch_span(plan, [r.rid for r in reqs]):
            try:
                with self._lock:
                    with self._span("agg.prepare"):
                        ent = self._prepare(plan)
                    outs = self._launch(ent, psig, [r.params for r in reqs])
                with self._span("agg.deliver"):
                    for r, out in zip(reqs, outs):
                        r.fut.set_result(out)
            except Exception as e:          # noqa: BLE001 — future carries it
                for r in reqs:
                    if not r.fut.done():
                        r.fut.set_exception(e)

    # -- plan preparation --------------------------------------------------
    @staticmethod
    def _grouped_root(plan: Plan):
        if isinstance(plan, GroupAgg):
            return plan, tuple(plan.keys)
        if isinstance(plan, AggCall) and plan.group_keys:
            return plan, tuple(plan.group_keys)
        return None, ()

    def _prepare(self, plan: Plan) -> _PlanEntry:
        ent = self._plans.get(id(plan))
        if ent is not None:
            return ent
        ent = _PlanEntry(submitted=plan, plan=plan)
        root, keys = self._grouped_root(plan)
        scan = root.child.table if (root is not None
                                    and isinstance(root.child, Scan)) else None
        # slot provisioning (and bound inference) require the grouped
        # node's input to BE a catalog table: row order and validity then
        # provably match what the slots were built from.  Anything else
        # (parameterized filters, joins) still gets the executable cache
        # and batching — slotting just happens inside the trace.
        if root is not None and scan is not None and scan in self._catalog:
            t = self._catalog[scan]
            if all(k in t.columns for k in keys):
                declared = root.max_groups if root.max_groups is not None \
                    else t.group_bound
                if declared is None and self._infer_bounds:
                    est = keyslot.distinct_count_sketch(t, keys)
                    mg = int(math.ceil(est * _SKETCH_PAD)) + _SKETCH_SLACK
                    _, bound = resolve_group_bound(mg, t.capacity)
                    if bound is not None:
                        ent.plan = _dc_replace(plan, max_groups=mg)
                        ent.inferred = True
                        declared = mg
                if declared is not None:
                    _, bound = resolve_group_bound(declared, t.capacity)
                    # provision slots exactly when the engine, handed
                    # them, takes the sort-free route
                    if bound is not None and grouped_route(
                            ent.plan, t, cached=True).sortfree:
                        ent.keys = keys
                        ent.bound = bound
                        ent.slot_scan = scan
                ent.shard_local = grouped_route(
                    ent.plan, t,
                    cached=ent.slot_scan is not None).merge is not None
        self._plans[id(plan)] = ent
        return ent

    # -- slot-table cache --------------------------------------------------
    def _slot_table(self, ent: _PlanEntry):
        t = self._catalog[ent.slot_scan]
        stale = 0
        while True:
            key = (ent.slot_scan, t.version, ent.keys, ent.bound)
            got = self._slots.get(key)
            if got is not None:
                tag, arrs, _state = got
                if tag == t.version:
                    self._bump("slot_hits")
                    return arrs
                # the entry claims a version the catalog no longer holds —
                # structurally impossible (the key carries the version)
                # without corruption/injection.  Never serve it: drop and
                # rebuild, bounded, then surface SlotTableStale.
                del self._slots[key]
                self._gbump("stale_rebuilds")
                stale += 1
                if stale > _MAX_STALE_REBUILDS:
                    raise SlotTableStale(
                        f"slot table for {ent.slot_scan!r} keeps claiming a "
                        f"dead Table.version after {stale - 1} rebuilds")
                continue
            try:
                if self._extend_slots(ent, t) is not None:
                    continue    # cached under the live key: take the hit path
                seg, owner, overflowed, state = keyslot.slot_state_build(
                    t, ent.keys, ent.bound)
                if not faults.fire("bound_unvalidated"):
                    check_slot_overflow(overflowed, ent.bound)  # concrete
                occupied = jnp.arange(ent.bound, dtype=jnp.int32) < state.cnt
                arrs = tuple(jax.block_until_ready(a)
                             for a in (seg, owner, occupied, overflowed))
                self._bump("slot_builds")
                tag = t.version - 1 if faults.fire("slot_stale") \
                    else t.version
                self._slots[key] = (tag, arrs, state)
                if stale:
                    continue    # recovering: re-prove the tag via the hit path
                return arrs
            except GroupBoundOverflow:
                if not ent.inferred:
                    raise        # user-declared bound: the contract raises
                # inferred bound overflowed (data grew / sketch undershot):
                # double it, re-bucket, rebuild — or give the bound up when
                # the bucket reaches the row capacity
                grown = ent.bound * 2
                _, bound = resolve_group_bound(grown, t.capacity)
                ent.execs.clear()
                if bound is None:
                    ent.plan = _dc_replace(ent.plan, max_groups=None)
                    ent.bound = None
                    ent.slot_scan = None
                    return None
                ent.plan = _dc_replace(ent.plan, max_groups=grown)
                ent.bound = bound

    def _extend_slots(self, ent: _PlanEntry, t: Table):
        """Extend a cached ancestor slot table across the pending
        ``append_rows`` chain instead of rebuilding: O(batch) per append
        step (slot the new rows against the resident ``SlotState``, patch
        ``seg`` at their positions, merge freshly claimed owners) vs the
        O(table) full rebuild.  Returns the new slot arrays cached under
        the live version, or None when no extendable ancestor exists
        (then the caller falls back to ``slot_state_build``)."""
        chain = []
        v = t.version
        while True:
            got = self._slots.get((ent.slot_scan, v, ent.keys, ent.bound))
            if got is not None and got[0] == v and got[2] is not None:
                break
            step = self._appends.get((ent.slot_scan, v))
            if step is None:
                return None
            pv, pos = step
            chain.append(pos)
            v = pv
        if not chain:
            return None
        akey = (ent.slot_scan, v, ent.keys, ent.bound)
        _tag, (seg, owner, _occ, _ovf), state = self._slots[akey]
        seg = jnp.asarray(seg)
        owner = jnp.asarray(owner)
        mask = t.mask()
        for pos in reversed(chain):             # oldest append first
            posj = jnp.asarray(pos, jnp.int32)
            nb = int(posj.shape[0])
            words = keyslot.key_words_for(
                jnp.take(t.columns[k], posj, axis=0) for k in ent.keys)
            bvalid = jnp.take(mask, posj)
            segb, new_owner, ovf, state = keyslot.slot_ids_extend(
                words, bvalid, state)
            check_slot_overflow(ovf, ent.bound)  # concrete: raises
            owner = jnp.where(
                new_owner < nb,
                jnp.take(posj, jnp.clip(new_owner, 0, nb - 1)),
                owner).astype(jnp.int32)
            if seg.shape[0] < t.capacity:        # capacity grew on append
                seg = jnp.concatenate(
                    [seg, jnp.full((t.capacity - seg.shape[0],),
                                   ent.bound, jnp.int32)])
            seg = seg.at[posj].set(segb)
            self._bump("slot_extends")
        occupied = jnp.arange(ent.bound, dtype=jnp.int32) < state.cnt
        arrs = tuple(jax.block_until_ready(a)
                     for a in (seg, owner, occupied, jnp.int32(0)))
        del self._slots[akey]                    # superseded ancestor
        self._slots[(ent.slot_scan, t.version, ent.keys, ent.bound)] = (
            t.version, arrs, state)
        return arrs

    # -- executables -------------------------------------------------------
    def _catalog_sig(self):
        return tuple(
            (name, t.group_bound, t.row_split, t.valid is None,
             tuple((c, str(a.dtype), tuple(a.shape))
                   for c, a in sorted(t.columns.items())))
            for name, t in sorted(self._catalog.items()))

    @staticmethod
    def _psig(params: Mapping[str, Any]):
        return tuple(sorted((k, str(jnp.result_type(v)))
                            for k, v in params.items()))

    def _executable(self, ent: _PlanEntry, psig, nb: int,
                    degraded: bool = False):
        key = (self._catalog_sig(), psig, nb, ent.bound, degraded)
        fn = ent.execs.get(key)
        if fn is None:
            fn = self._build(ent, psig, nb, degraded)
            ent.execs[key] = fn
        return fn

    def _build(self, ent: _PlanEntry, psig, nb: int, degraded: bool = False):
        plan = ent.plan
        spec = (ent.keys, ent.bound) if ent.slot_scan is not None else None
        stats = self.stats

        def run(tables, slots, pvec):
            stats.traces += 1    # Python side effect: counts traces only
            # the body below runs only while tracing, so the degraded
            # executable's force_backend scope is active exactly when the
            # backend choice bakes into the jaxpr — every kernel-backend
            # resolution in the trace lowers to the jnp segment-ops path
            ctx = degrade.force_backend("jnp") if degraded else nullcontext()

            def one(env):
                if spec is None:
                    return execute(plan, tables, env)
                with keyslot.provide_slots({spec: slots}):
                    return execute(plan, tables, env)

            with ctx:
                if not psig:
                    return one({})
                return jax.vmap(one)(pvec)

        return jax.jit(run)

    # -- launch ------------------------------------------------------------
    def _launch(self, ent: _PlanEntry, psig, plist):
        """Run a same-signature request batch through one (possibly
        vmapped) cached launch per max_batch bucket; returns one Table
        per request.  Under the guard each bucket goes through the
        poison scan / retry / breaker ladder."""
        n = len(plist)
        outs = []
        for start in range(0, n, self._max_batch):
            chunk = plist[start:start + self._max_batch]
            outs.extend(self._guarded_bucket(ent, psig, chunk)
                        if self._guard
                        else self._launch_bucket(ent, psig, chunk))
        # the auxiliary bool-only poison stamp is serving-internal: the
        # guarded scan above has read it; callers get their own columns
        return [strip_poison_stamp(o) if isinstance(o, Table) else o
                for o in outs]

    def _bucket_args(self, ent: _PlanEntry, psig, plist):
        """(batch bucket, executable arguments) for one request bucket."""
        slots = ()
        if ent.slot_scan is not None:
            with self._span("agg.prepare"):
                got = self._slot_table(ent)   # may grow/disable the bound
            slots = got if got is not None else ()
        if not psig:
            return 1, (self._catalog, slots, {})
        with self._span("agg.args"):
            nb = 1 << (len(plist) - 1).bit_length()
            padded = plist + [plist[-1]] * (nb - len(plist))  # pad lanes
            pvec = {k: jnp.asarray(np.stack([np.asarray(p[k])
                                             for p in padded]))
                    for k, _ in psig}
        return nb, (self._catalog, slots, pvec)

    def _launch_bucket(self, ent: _PlanEntry, psig, plist,
                       degraded: bool = False):
        n = len(plist)
        nb, args = self._bucket_args(ent, psig, plist)
        fn = self._executable(ent, psig, nb, degraded)
        self._bump("requests", n)
        self._bump("batches")
        if ent.shard_local:
            self._bump("sharded_launches")
        if degraded:
            self._gbump("degraded_launches")
        if not degraded:
            faults.fail("backend_exc")
        with self._span("agg.dispatch"):     # enqueue, or trace + compile
            out = fn(*args)
        if not psig:
            return [out] * n
        with self._span("agg.unbatch"):
            return [jax.tree_util.tree_map(lambda a, i=i: a[i], out)
                    for i in range(n)]    # padded lanes dropped

    def compiled_text(self, plan: Plan,
                      params: Optional[Mapping[str, Any]] = None) -> str:
        """Optimized HLO text of the primary executable serving one
        ``(plan, params)`` request — what the device runs, so a caller can
        check which kernels it holds (``tpu_custom_call`` on TPU).
        Lowers with the launch's own arguments, so an executable that
        already ran is neither retraced nor recompiled."""
        params = dict(params or {})
        with self._lock:
            ent = self._prepare(plan)
            psig = self._psig(params)
            nb, args = self._bucket_args(ent, psig, [params])
            fn = self._executable(ent, psig, nb)
        return fn.lower(*args).compile().as_text()

    # -- guarded launch ----------------------------------------------------
    def _breaker(self, ent: _PlanEntry, psig) -> CircuitBreaker:
        key = (id(ent.submitted), psig)
        br = self._breakers.get(key)
        if br is None:
            br = CircuitBreaker(
                self._breaker_threshold, self._breaker_cooldown,
                self._breaker_clock)
            # insertion under the stats mutex: describe() iterates the
            # breaker dict lock-free of the big server lock
            with self._stats_lock:
                br = self._breakers.setdefault(key, br)
        return br

    def _guarded_bucket(self, ent: _PlanEntry, psig, plist):
        """One bucket launch under the full failure contract: typed
        errors out, never raw backend exceptions or silent poison.

        Ladder, in order: a backend exception from the primary
        executable records on the (plan, signature) breaker and the
        batch immediately re-runs on the degraded jnp executable (the
        request is served; only a failure of the fallback too surfaces
        ``BackendFailure``).  A result carrying the poison stamp —
        a traced bound check failed inside the launch — retries with a
        doubled bound when the bound was inferred (bounded, with a
        rebuild backoff) and surfaces ``PoisonedResult`` otherwise."""
        br = self._breaker(ent, psig)
        attempts = 0
        while True:
            degraded = br.use_degraded()
            try:
                outs = self._launch_bucket(ent, psig, plist,
                                           degraded=degraded)
                if not degraded and br.record_success():
                    self._gbump("breaker_recoveries")
            except GroupBoundOverflow as e:
                raise BoundOverflow(str(e)) from e
            except ServeError:
                raise
            except Exception as e:          # noqa: BLE001 — ladder absorbs
                if degraded:
                    raise BackendFailure(
                        "degraded (jnp) launch failed") from e
                self._gbump("backend_failures")
                self._log_absorbed((id(ent.submitted), psig),
                                   type(ent.submitted).__name__, e)
                if br.record_failure():
                    self._gbump("breaker_trips")
                try:
                    outs = self._launch_bucket(ent, psig, plist,
                                               degraded=True)
                except GroupBoundOverflow as e2:
                    raise BoundOverflow(str(e2)) from e2
                except ServeError:
                    raise
                except Exception as e2:     # noqa: BLE001
                    raise BackendFailure(
                        "kernel backend failed and the degraded (jnp) "
                        "fallback failed too") from e2
            # the scan below blocks on the device anyway: waiting first
            # splits the device's time from the scan's host work
            with self._span("agg.await"):
                jax.block_until_ready(outs)
            # poison scan: O(num_segments) per distinct result Table
            # (parameterless batches share one object — scan it once)
            seen: Dict[int, bool] = {}
            poisoned = False
            with self._span("agg.guard_scan"):
                for out in outs:
                    if id(out) not in seen:
                        seen[id(out)] = is_poisoned(out)
                    poisoned = poisoned or seen[id(out)]
            if not poisoned:
                return outs
            self._gbump("poisoned")
            if (not ent.inferred or ent.bound is None
                    or attempts >= _MAX_POISON_RETRIES):
                raise PoisonedResult(
                    "launch output carries the poison stamp: a traced "
                    "dense group bound check failed inside the "
                    "executable — raise max_groups or drop the "
                    "declaration")
            # inferred bound: double, rebuild, relaunch (bounded)
            attempts += 1
            self._gbump("poison_retries")
            time.sleep(0.001 * attempts)    # brief rebuild backoff
            self._grow_bound(ent)

    def _grow_bound(self, ent: _PlanEntry) -> None:
        """Double an inferred bound after a poisoned launch: drop the
        slot tables built for the old bucket, clear the executables (the
        segment range is part of their shapes), and re-bucket — or give
        the bound up entirely once the bucket reaches the row capacity
        (capacity-sized tensors cannot overflow, so poison cannot
        recur)."""
        t = self._catalog[ent.slot_scan] if ent.slot_scan else None
        old = ent.bound
        grown = old * 2
        _, bound = resolve_group_bound(grown, t.capacity if t is not None
                                       else grown + 2)
        ent.execs.clear()
        self._slots = {k: v for k, v in self._slots.items()
                       if not (k[0] == ent.slot_scan and k[2] == ent.keys
                               and k[3] == old)}
        if bound is None:
            ent.plan = _dc_replace(ent.plan, max_groups=None)
            ent.bound = None
            ent.slot_scan = None
        else:
            ent.plan = _dc_replace(ent.plan, max_groups=grown)
            ent.bound = bound
