"""Plan execution in JAX over columnar Tables.

Every operator keeps the fixed-capacity + validity-mask representation, so
the whole plan compiles to one XLA program (no host round trips): this is
what realizes the paper's "single pipelined query execution" claim for the
rewritten form.  The cursor baseline, by contrast, calls ``materialize()``
between the query and the loop — the temp-table barrier.
"""
from __future__ import annotations

import os
from typing import Any, Mapping, NamedTuple, Optional

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax

from repro.core.loop_ir import eval_expr
from repro.configs import flags
from .plan import (AggCall, Filter, GroupAgg, IterSpace, Join, Limit, OrderBy,
                   Plan, Project, Scan)
from .table import Table

Catalog = Mapping[str, Table]
Env = Mapping[str, Any]


def execute(plan: Plan, catalog: Catalog, env: Optional[Env] = None) -> Table:
    env = dict(env or {})
    return _exec(plan, catalog, env)


def _col_env(t: Table, env: Env) -> dict[str, Any]:
    e = dict(env)
    e.update(t.columns)
    return e


def _exec(plan: Plan, catalog: Catalog, env: Env) -> Table:
    if isinstance(plan, Scan):
        return catalog[plan.table]

    if isinstance(plan, IterSpace):
        init = jnp.asarray(eval_expr(plan.init, env))
        bound = jnp.asarray(eval_expr(plan.bound, env))
        step = jnp.asarray(eval_expr(plan.step, env))
        idx = init + jnp.arange(plan.capacity, dtype=init.dtype) * step
        ok = (idx <= bound) if plan.inclusive else (idx < bound)
        # descending iteration (negative step)
        ok_desc = (idx >= bound) if plan.inclusive else (idx > bound)
        ok = jnp.where(step < 0, ok_desc, ok)
        return Table({plan.column: idx}, ok)

    if isinstance(plan, Filter):
        t = _exec(plan.child, catalog, env)
        mask = eval_expr(plan.pred, _col_env(t, env))
        return t.filter(jnp.asarray(mask, dtype=bool))

    if isinstance(plan, Project):
        t = _exec(plan.child, catalog, env)
        cenv = _col_env(t, env)
        cols = {}
        for name, e in plan.exprs:
            v = eval_expr(e, cenv)
            v = jnp.broadcast_to(jnp.asarray(v), (t.capacity,) + jnp.shape(jnp.asarray(v))[1:]) \
                if jnp.ndim(jnp.asarray(v)) == 0 else jnp.asarray(v)
            cols[name] = v
        # computed expressions can mint columns with more distinct values
        # than the declared group bound covers; only pure column renames
        # keep the declaration honest
        from repro.core.loop_ir import Col as _Col
        keep = t.group_bound if all(isinstance(e, _Col)
                                    for _, e in plan.exprs) else None
        return Table(cols, t.valid, keep, row_split=t.row_split)

    if isinstance(plan, Join):
        lt = _exec(plan.left, catalog, env)
        rt = _exec(plan.right, catalog, env)
        return _gather_join(lt, rt, plan.left_key, plan.right_key, plan.how)

    if isinstance(plan, OrderBy):
        t = _exec(plan.child, catalog, env)
        return t.sort_by(plan.keys, plan.descending)

    if isinstance(plan, Limit):
        # first-n valid rows by prefix sum of the validity mask — an
        # in-place mask intersection, never a compaction (the old
        # compress()-based lowering paid a row-sized stable sort + gather
        # just to drop a mask; see analysis/jaxpr_spy.limit_census)
        t = _exec(plan.child, catalog, env)
        keep = jnp.cumsum(t.mask().astype(jnp.int32)) <= plan.n
        return t.filter(keep)

    if isinstance(plan, GroupAgg):
        from . import fuse
        needed = plan.keys + _agg_cols(plan.aggs)
        res = fuse.fused_chain_result(plan.child, catalog, env,
                                      tuple(needed), _exec)
        if res is None:
            t = _exec(plan.child, catalog, env)
            return _group_agg(t, plan.keys, plan.aggs, plan.max_groups)
        slots = _probe_slot_mapping(res, plan.keys, plan.max_groups)
        if slots is None:
            return _group_agg(res.table, plan.keys, plan.aggs,
                              plan.max_groups)
        from .keyslot import provide_slots
        with provide_slots(slots):
            return _group_agg(res.table, plan.keys, plan.aggs,
                              plan.max_groups)

    if isinstance(plan, AggCall):
        # Import here: core.executors depends on this module.
        from repro.core.executors import execute_agg_call
        return execute_agg_call(plan, catalog, env)

    raise TypeError(f"unknown plan node {type(plan)}")


def _agg_cols(aggs) -> tuple[str, ...]:
    """Column names a GroupAgg aggs tuple reads (arg-extremum ops read a
    (key, payload) pair; count reads none)."""
    cols: list[str] = []
    for _out, _op, col in aggs:
        if col is None:
            continue
        if isinstance(col, tuple):
            cols.extend(col)
        else:
            cols.append(col)
    return tuple(cols)


def execute_for_agg(child: Plan, catalog: Catalog, env: Env,
                    needed: tuple) -> Table:
    """Execute an aggregate's child plan, fusing a
    ``Filter*/Project* → Join`` chain into the aggregate input when it
    matches (relational/fuse.py): the join runs as a lookup only,
    predicates fold into the validity mask the kernel sees as its guard,
    and only the ``needed`` columns materialize.  Anything unmatched
    falls back to per-node execution — identical results either way
    (the fusion parity gates pin this)."""
    from . import fuse
    t = fuse.fused_child_table(child, catalog, env, tuple(needed), _exec)
    if t is None:
        t = _exec(child, catalog, env)
    return t


def _probe_slot_mapping(res, keys: tuple[str, ...],
                        max_groups) -> dict | None:
    """Turn a fused chain's join-probe outputs into a keyslot slot table
    for the downstream GroupAgg — the "probe results feed the kernel"
    leg of whole-plan fusion.

    When the aggregate groups by exactly the join's left key (inner
    join), the probe already assigned every valid row a consistent
    segment id: ``ridx`` — equal keys hit the same build slot, distinct
    keys cannot share one (slot ownership is verified on exact canonical
    key words).  Providing ``(seg, owner, occupied, overflowed=0)`` via
    keyslot.provide_slots lets _group_agg's sort-free branch skip the
    whole slot build/claim/verify loop — the aggregation kernel launches
    straight off the probe outputs, with the chain's guard mask as row
    validity.  Segment ids are right-table row numbers here (not
    claim-densified), so the bound must cover the right capacity;
    ``owner`` holds the smallest matching LEFT row per segment, which is
    what sortfree_result gathers the representative key values from.

    Returns None — plain slotting proceeds — for multi-key or non-inner
    chains, keys that do not resolve to the left join key, an undeclared
    bound, or a bound smaller than the right table."""
    chain = res.chain
    if chain.join.how != "inner" or len(keys) != 1:
        return None
    try:
        if chain.resolve(keys[0]) != chain.join.left_key:
            return None
    except KeyError:
        return None
    from .group_bound import resolve_group_bound
    t = res.table
    declared = max_groups if max_groups is not None else t.group_bound
    _, bound = resolve_group_bound(declared, t.capacity)
    if bound is None or res.right_capacity > bound:
        return None
    cap = t.capacity
    tv = t.mask()
    seg = jnp.where(tv, res.ridx, bound).astype(jnp.int32)
    rows = jnp.arange(cap, dtype=jnp.int32)
    owner = jnp.full((bound,), cap, jnp.int32).at[seg].min(
        jnp.where(tv, rows, cap), mode="drop")
    occupied = owner < cap
    return {(tuple(keys), bound): (seg, owner, occupied, jnp.int32(0))}


# ---------------------------------------------------------------------------
# Join
# ---------------------------------------------------------------------------


def join_hash_enabled() -> bool:
    """Kill switch for the sort-free keyslot hash join (default: on).
    ``REPRO_JOIN_HASH=off`` restores the legacy stable-argsort +
    searchsorted lookup."""
    return flags.enabled("REPRO_JOIN_HASH")


def _common_key_cast(lk: jax.Array, rk: jax.Array):
    """Harmonize the two key columns onto one exact comparison dtype.

    Deliberately *numpy's* promotion lattice: ``np.promote_types(int32,
    float32)`` is float64 (exact for every int32), where JAX's own
    lattice would answer float32 and silently round keys above 2^24 —
    the historical ``lk.astype(rk.dtype)`` exactness bug.  Limitation:
    64-bit promotions need x64 enabled to take effect (JAX downgrades
    the cast otherwise), and int64 keys beyond 2^53 promoted against a
    float side are inexact in any float dtype.
    """
    if lk.dtype == rk.dtype:
        return lk, rk
    d = jnp.dtype(np.promote_types(lk.dtype, rk.dtype))
    return lk.astype(d), rk.astype(d)


def _sorted_lookup(lk: jax.Array, rk: jax.Array, rvalid: jax.Array,
                   ) -> tuple[jax.Array, jax.Array]:
    """Legacy lookup: sort right by key (invalid rows to +inf),
    binary-search each left key, verify equality + right validity."""
    rk_sortkey = _key_for_search(rk, rvalid)
    # stable, explicitly: searchsorted lands on the LEFTMOST equal sorted
    # key, so with a stable order a (contract-violating) duplicate right
    # key deterministically picks the smallest original row index —
    # matching sort_by's stability contract instead of whatever an
    # unstable sort happened to place first
    order = jnp.argsort(rk_sortkey, stable=True)
    rk_sorted = jnp.take(rk_sortkey, order)
    pos = jnp.searchsorted(rk_sorted, lk)
    pos = jnp.clip(pos, 0, rk.shape[0] - 1)
    ridx = jnp.take(order, pos)
    found = (jnp.take(rk, ridx) == lk) & jnp.take(rvalid, ridx)
    return ridx, found


def _hash_lookup(lk: jax.Array, rk: jax.Array, rvalid: jax.Array,
                 ) -> tuple[jax.Array, jax.Array]:
    """Sort-free lookup on the keyslot hash table: build on the right
    keys' canonical words, probe one walk per left row.  No row-sized
    sort or gather — the probe loop's per-round gathers are a handful of
    static equations regardless of row count."""
    from . import keyslot
    ridx, found = keyslot.build_probe(
        keyslot.key_words_for([rk]), rvalid, keyslot.key_words_for([lk]))
    if jnp.issubdtype(lk.dtype, jnp.floating):
        # canonical words equate NaN per bit pattern (grouping
        # semantics); join equality is VALUE equality, where NaN never
        # matches — mask it back out, mirroring the sorted route's
        # ``rk == lk`` verification
        found = found & (lk == lk)
    return ridx, found


def _join_lookup(lt: Table, rt: Table, lkey: str, rkey: str,
                 ) -> tuple[jax.Array, jax.Array]:
    """Resolve each left row against the unique-keyed right side.

    Returns ``(ridx, found)``: ``ridx`` (capacity,) int32 right-row
    indices (clip-safe sentinel where unmatched), ``found`` (capacity,)
    bool — left rows with a valid right match.  This is the whole join
    *lookup*; materializing joined columns (``_apply_join``) is separate
    so the fusion pass can consume the lookup directly.
    """
    with jax.named_scope("join.probe"):
        lk, rk = _common_key_cast(lt.columns[lkey], rt.columns[rkey])
        if join_hash_enabled():
            return _hash_lookup(lk, rk, rt.mask())
        return _sorted_lookup(lk, rk, rt.mask())


def _apply_join(lt: Table, rt: Table, rkey: str, how: str,
                ridx: jax.Array, found: jax.Array) -> Table:
    """Materialize the joined Table from a ``_join_lookup`` result."""
    if how == "semi":
        return lt.filter(found)
    if how == "anti":
        return lt.filter(~found)

    gidx = jnp.clip(ridx, 0, rt.capacity - 1)
    cols = dict(lt.columns)
    for name, v in rt.columns.items():
        if name == rkey or name in cols:
            continue
        cols[name] = jnp.take(v, gidx, axis=0, mode="clip")
    if how == "inner":
        valid = lt.mask() & found
    elif how == "left":
        valid = lt.mask()
        # null out unmatched right columns (zeros)
        for name in rt.columns:
            if name == rkey or name in lt.columns:
                continue
            cols[name] = jnp.where(
                _bmask(found, cols[name]), cols[name],
                jnp.zeros_like(cols[name]))
    else:
        raise ValueError(f"unsupported join how={how}")
    # the join introduces right-side columns the left table's declared
    # bound never covered — grouping the result by one of them could have
    # arbitrarily many groups, so the declaration must not survive
    # (semi/anti joins returned earlier: they keep the left columns only);
    # the rows are the left table's, and so is their split
    return Table(cols, valid, row_split=lt.row_split)


def _gather_join(lt: Table, rt: Table, lkey: str, rkey: str, how: str) -> Table:
    """Join against a unique-keyed right side: hash lookup on the keyslot
    table by default (``_hash_lookup``), the legacy argsort +
    searchsorted route under ``REPRO_JOIN_HASH=off``."""
    ridx, found = _join_lookup(lt, rt, lkey, rkey)
    return _apply_join(lt, rt, rkey, how, ridx, found)


def _bmask(m: jax.Array, v: jax.Array) -> jax.Array:
    return m.reshape(m.shape + (1,) * (v.ndim - 1))


def _key_for_search(k: jax.Array, valid: jax.Array) -> jax.Array:
    if jnp.issubdtype(k.dtype, jnp.floating):
        return jnp.where(valid, k, jnp.inf).astype(k.dtype)
    big = jnp.iinfo(k.dtype).max
    return jnp.where(valid, k, big)


# ---------------------------------------------------------------------------
# Grouped built-in aggregation
# ---------------------------------------------------------------------------


def segment_ids_for(t: Table, keys: tuple[str, ...],
                    num_segments: Optional[int] = None
                    ) -> tuple[Table, jax.Array, jax.Array]:
    """Sort by group keys and derive segment ids.  Returns (sorted table,
    segment_ids, segment_starts_mask).  ``num_segments`` is the static
    segment range the ids must stay within (default: row capacity);
    invalid rows park in its last slot — the dedicated overflow segment
    when a dense group bound is declared (group_bound.resolve_group_bound
    reserves it), the legacy capacity-1 slot otherwise."""
    st = t.sort_by(keys)
    m = st.mask()
    starts = group_starts(st, keys)
    seg = jnp.cumsum(starts.astype(jnp.int32)) - 1
    overflow = (st.capacity if num_segments is None else num_segments) - 1
    seg = jnp.where(m, seg, overflow)  # park invalid rows in the last seg
    return st, seg, starts


def group_starts(st: Table, keys: tuple[str, ...]) -> jax.Array:
    """The valid rows of ``st`` (sorted by ``keys``) that start a group:
    the first, and each whose keys differ from the row before."""
    m = st.mask()
    same = jnp.ones(st.capacity, dtype=bool)
    for k in keys:
        c = st.columns[k]
        eq = keys_equal(c[1:], c[:-1])
        same = same & jnp.concatenate([jnp.array([False]), eq])
    return m & ~same


def segment_keys(st: Table, starts: jax.Array, seg: jax.Array,
                 keys: tuple[str, ...], width: int) -> dict[str, jax.Array]:
    """The key columns of groups ``0..width-1`` of ``st``, its rows
    sorted by ``keys`` with ``starts`` marking each group's first row and
    ``seg`` numbering the groups: one ``(width,)`` array per key, each
    group's key bit for bit.

    A stream compaction by ONE unstable ``lax.sort`` (scope
    ``segment_keys``): the sort key is the group number on start rows
    and ``width`` on every other row, so the start rows come first, in
    group order, whatever the order among the rest; the key columns ride
    as payloads in their own dtypes (NaN, -0.0 and bool keys stay
    exact).  No op takes one element per row: on a TPU v5e a scatter or
    gather of one element per row costs about 12 ns a row, a sort with
    one payload 1-1.5 ns.  Entries past the last group hold the key of
    ``st``'s last row (zero for an empty table), as a gather of each
    segment's first row, clipped to the rows, read."""
    cap = st.capacity
    cols = [st.columns[k] for k in keys]
    with jax.named_scope("segment_keys"):
        ids = jnp.where(starts, seg, width).astype(jnp.int32)
        res = lax.sort((ids, *cols), dimension=0, is_stable=False,
                       num_keys=1)
    pad = max(width - cap, 0)
    ids = jnp.pad(res[0][:width], (0, pad), constant_values=width)
    got = ids < width
    out = {}
    for k, c, r in zip(keys, cols, res[1:]):
        fill = c[cap - 1] if cap else jnp.zeros((), c.dtype)
        out[k] = jnp.where(got, jnp.pad(r[:width], (0, pad)), fill)
    return out


def keys_equal(a: jax.Array, b: jax.Array) -> jax.Array:
    """Elementwise: the key values ``a`` and ``b`` fall in one group."""
    eq = a == b
    if jnp.issubdtype(a.dtype, jnp.floating):
        # NaN keys group per bit pattern, as the hash-slotted route's
        # canonical words do (the sort makes equal NaNs adjacent)
        from .keyslot import canonical_key_words
        nan = jnp.isnan(a) & jnp.isnan(b)
        for wa, wb in zip(canonical_key_words(a), canonical_key_words(b)):
            nan = nan & (wa == wb)
        eq = eq | nan
    return eq


_SEG_OPS = {
    "sum": jax.ops.segment_sum,
    "min": jax.ops.segment_min,
    "max": jax.ops.segment_max,
    "prod": jax.ops.segment_prod,
}

#: ops the fused Pallas segment-aggregate kernel serves from its moment
#: rows (mean = sum/count; argmin/argmax = extremum + index moment)
_FUSED_OPS = ("sum", "min", "max", "count", "mean", "argmin", "argmax")

#: arg-extremum GroupAgg ops: col is a (key_col, payload_col) pair and the
#: output is the payload value of the FIRST row attaining the key extremum
#: within the group (strict-comparison tie order, matching a cursor
#: loop's ``If(key < best)``)
_ARG_OPS = ("argmin", "argmax")


def _groupagg_fused_backend() -> Optional[str]:
    """Backend for the fused GroupAgg path: None for per-op jnp segment
    ops, "off" for an explicit kill switch (also disables sharded
    routing).  Default: the compiled kernel on TPU (one HBM pass for all
    moments), per-op jnp elsewhere.  REPRO_GROUPAGG_FUSED ∈ {pallas,
    interpret, jnp, off} overrides (tests use 'interpret'); a
    thread-local ``reliability.degrade.force_backend`` scope beats both
    — the serving circuit breaker traces degraded executables under
    it."""
    from ..configs import flags
    from ..reliability.degrade import forced_backend
    forced = forced_backend()
    if forced is not None:
        return forced
    env = flags.choice("REPRO_GROUPAGG_FUSED",
                       ("pallas", "interpret", "jnp", "off"))
    if env is not None:
        return env
    return "pallas" if jax.default_backend() == "tpu" else None


class GroupRoute(NamedTuple):
    """How a grouped node (``GroupAgg`` or grouped ``AggCall``) runs,
    decided from static shapes, dtypes and the declared row split only.
    ``sortfree``: hash-slotted segment ids in the kernel's unsorted
    layout instead of the group sort.  ``kernel``: some aggregate runs on
    the fused segment-aggregate kernel (None when a call's parameters
    were not given).  ``rows``: rows one kernel launch walks (a shard's
    slice when the input is row-split).  ``num_segments``: the segment
    range.  ``merge``: on a row-split input, how the group sort runs
    shard by shard and its partial groups merge across devices
    (``launch.sharded_agg.shard_local_merge``); None where the sort is
    one sort of every row."""
    sortfree: bool
    kernel: Optional[bool]
    rows: int
    num_segments: int
    merge: Optional[str] = None

    @property
    def name(self) -> str:
        return "sort-free" if self.sortfree else "sorted"

    @property
    def grid_steps(self) -> int:
        """Kernel grid steps one launch runs; 0 off the kernel."""
        from repro.kernels.segment_agg import (full_grid_steps,
                                               launched_grid_steps)
        if not self.kernel:
            return 0
        steps = full_grid_steps if self.sortfree else launched_grid_steps
        return steps(self.rows, self.num_segments)


def grouped_route(plan: Plan, t: Table, env: Optional[Env] = None, *,
                  cached: Optional[bool] = None) -> GroupRoute:
    """The route ``execute`` takes for the grouped node ``plan`` over its
    input table ``t`` (concrete or abstract — ``jax.eval_shape`` of the
    child plan will do).  ``env`` holds a grouped ``AggCall``'s
    parameters (only their dtypes are read); ``cached`` says whether a
    provided slot table (``keyslot.provide_slots``) covers the node, None
    reading the active scope.  The executors decide through the same
    functions, so a caller that provisions for the route (the serving
    layer's slot cache) or reports it cannot disagree with the run."""
    if isinstance(plan, GroupAgg):
        return _group_agg_setup(t, plan.keys, plan.aggs, plan.max_groups,
                                cached).route
    from repro.core.executors import grouped_call_route
    return grouped_call_route(plan, t, env, cached=cached)


def kernel_grid_allows_sortfree(backend: Optional[str], rows: int,
                                num_segments: int) -> bool:
    """The sort-free route's kernel-grid gate: with a kernel backend, the
    unsorted cross-product grid over ``rows`` rows per launch must stay
    within ``SORTFREE_GRID_RATIO`` × the sorted pruned grid
    (``segment_agg.sortfree_grid_ok``); jnp segment ops have no grid, so
    they always pass."""
    from repro.kernels.segment_agg import resolve_backend, sortfree_grid_ok
    if backend in (None, "off") or resolve_backend(backend) == "jnp":
        return True
    return sortfree_grid_ok(rows, num_segments)


class _GroupAggSetup(NamedTuple):
    backend: Optional[str]
    nsegments: int
    bound: Optional[int]
    shard_route: Optional[tuple]
    cached: bool
    fused_aggs: list
    rest_aggs: tuple
    route: GroupRoute


def _group_agg_setup(t: Table, keys: tuple[str, ...], aggs,
                     max_groups: Optional[int],
                     cached: Optional[bool] = None) -> _GroupAggSetup:
    """Every static decision of ``_group_agg``: backend, segment range,
    row split, which ops ride the fused kernel pass, and the route."""
    from repro.launch.sharded_agg import (launch_rows, shard_local_merge,
                                          table_row_split)
    from .group_bound import resolve_group_bound
    from .keyslot import provided_slots, sortfree_enabled
    backend = _groupagg_fused_backend()
    # dense segment range: plan-declared max_groups beats the table hint;
    # without either, the row capacity is the only static bound available
    declared = max_groups if max_groups is not None else t.group_bound
    nsegments, bound = resolve_group_bound(declared, t.capacity)
    cap = t.capacity
    # a row-split input table (Table.shard_rows) routes the fused pass
    # through the mesh — one kernel launch per row shard, moments
    # all-reduced.  A provide_slots scope carrying this call's slot table
    # replaces the launcher's per-shard slotting: the cached assignment
    # is GLOBAL (stable across calls), so each shard aggregates onto it
    # directly.
    shard_route = None
    if backend != "off":
        shard_route = table_row_split(t)
        if backend is None and shard_route is not None:
            backend = "auto"    # distributed beats per-op even off-TPU
    if cached is None:
        cached = bound is not None and provided_slots(keys, bound) is not None

    def _fusable(op, col):
        # kernel accumulates in f32: float64 columns keep the exact per-op
        # path, and counts (f32-exact only below 2^24) require the row
        # capacity to bound every segment count inside that range
        if op not in _FUSED_OPS:
            return False
        if op in ("count", "mean") and cap >= 1 << 24:
            return False
        if op in _ARG_OPS:
            # key compare + attaining-row index both run in f32: the key
            # column must embed exactly (≤32-bit float / ≤16-bit int) and
            # every (padded) row index must be f32-exact — the same gate
            # the kernel validates
            from repro.core.executors import _f32_exact_key_dtype
            from repro.kernels.segment_agg import index_moment_ok
            return (index_moment_ok(cap)
                    and _f32_exact_key_dtype(t.columns[col[0]].dtype))
        if col is None:
            return True
        d = t.columns[col].dtype
        return jnp.issubdtype(d, jnp.floating) and jnp.dtype(d).itemsize <= 4

    fused_aggs = [] if backend in (None, "off") else [
        (out, op, col) for out, op, col in aggs if _fusable(op, col)]
    rest_aggs = tuple(a for a in aggs if a not in fused_aggs)

    # SORT-FREE route: every GroupAgg op is an order-insensitive moment
    # (commutative merge algebra), so whenever a dense bound is declared
    # the hash-slotted segment assignment (relational/keyslot.py) replaces
    # the group sort — unless the kernel's unsorted grid would dwarf the
    # sorted one.  Sharded inputs without cached slots additionally need
    # every op on the fused pass — slots are assigned per shard inside
    # the launcher, so the per-op segment fallbacks have no global ids.
    rows = launch_rows(cap, shard_route)
    sortfree = (bound is not None and sortfree_enabled()
                and not (shard_route is not None and not cached
                         and (rest_aggs or not fused_aggs))
                and (not fused_aggs or kernel_grid_allows_sortfree(
                    backend, rows, nsegments)))
    from repro.kernels.segment_agg import resolve_backend
    kernel = bool(fused_aggs) and resolve_backend(backend) != "jnp"
    merge = None if sortfree else shard_local_merge(t, keys, bound,
                                                    shard_route)
    return _GroupAggSetup(backend, nsegments, bound, shard_route, cached,
                          fused_aggs, rest_aggs,
                          GroupRoute(sortfree, kernel, rows, nsegments,
                                     merge))


def _group_agg(t: Table, keys: tuple[str, ...],
               aggs: tuple[tuple[str, str, Optional[str]], ...],
               max_groups: Optional[int] = None) -> Table:
    from .group_bound import check_group_overflow, poison_overflow
    from .keyslot import (overflow_extended, slot_segment_ids,
                          sortfree_result)
    (backend, nsegments, bound, shard_route, cached, fused_aggs, rest_aggs,
     route) = _group_agg_setup(t, keys, aggs, max_groups)
    sortfree = route.sortfree
    cap = t.capacity

    cols: dict[str, jax.Array] = {}
    if sortfree and shard_route is not None and not cached:
        out, (rep, out_valid, unplaced) = _group_agg_fused(
            t, None, t.mask(), nsegments, fused_aggs, backend,
            shard_route=shard_route, sortfree_keys=keys)
        return sortfree_result(t, keys, rep, out_valid, unplaced, bound,
                               out)

    seg_map = None
    if sortfree:
        st, m = t, t.mask()
        seg, owner, occupied, unplaced = slot_segment_ids(t, keys, bound)
        # occupied is a dense CLAIM-order prefix (not key order); key
        # representatives, validation, and poisoning all happen in the
        # shared sortfree_result epilogue after the aggregates compute
        rep, out_valid = overflow_extended(owner, occupied, cap)
        layout = "unsorted"
    elif route.merge is not None:
        from repro.launch.sharded_agg import shard_local_segments
        ss = shard_local_segments(
            t, keys, nsegments, bound, route.merge, *shard_route,
            stable=any(op in _ARG_OPS for _out, op, _col in aggs))
        st, seg, seg_map, m = ss.table, ss.seg, ss.seg_map, ss.table.mask()
        overflow_ok = ss.guard()
        out_valid = jnp.arange(nsegments) < ss.nseg
        cols.update(ss.keys)
        layout = "sorted"
    else:
        st, seg, starts = segment_ids_for(t, keys, num_segments=nsegments)
        m = st.mask()
        nseg = jnp.sum(starts.astype(jnp.int32))
        overflow_ok = check_group_overflow(nseg, bound)
        out_valid = jnp.arange(nsegments) < nseg
        cols.update(segment_keys(st, starts, seg, keys, nsegments))
        layout = "sorted"

    if fused_aggs:
        cols.update(_group_agg_fused(st, seg, m, nsegments, fused_aggs,
                                     backend, shard_route=shard_route,
                                     layout=layout, seg_map=seg_map))
    aggs = rest_aggs
    if aggs and seg_map is not None:
        from repro.launch.sharded_agg import global_segment_ids
        seg = global_segment_ids(seg, seg_map, nsegments, *shard_route)

    for out, op, col in aggs:
        if op == "count":
            vals = m.astype(jnp.int64 if jax.config.jax_enable_x64 else jnp.int32)
            cols[out] = jax.ops.segment_sum(vals, seg,
                                            num_segments=nsegments)
            continue
        if op in _ARG_OPS:
            # per-op fallback (wide key dtypes / fused off): hit-detection
            # formulation in the key column's own dtype — exact
            kc, pc = col
            kv, pv = st.columns[kc], st.columns[pc]
            fill = _identity_for("min" if op == "argmin" else "max",
                                 kv.dtype)
            masked = jnp.where(m, kv, fill)
            segf = jax.ops.segment_min if op == "argmin" \
                else jax.ops.segment_max
            best = segf(masked, seg, num_segments=nsegments)
            hit = m & (masked == jnp.take(best, seg))
            cand = jnp.where(hit, jnp.arange(cap), cap)
            pick = jax.ops.segment_min(cand, seg, num_segments=nsegments)
            got = pick < cap
            cols[out] = jnp.where(
                got, jnp.take(pv, jnp.clip(pick, 0, cap - 1)),
                jnp.zeros((), pv.dtype))
            continue
        v = st.columns[col]
        if op == "mean":
            s = jax.ops.segment_sum(jnp.where(m, v, 0).astype(jnp.float32), seg,
                                    num_segments=nsegments)
            c = jax.ops.segment_sum(m.astype(jnp.float32), seg,
                                    num_segments=nsegments)
            cols[out] = s / jnp.maximum(c, 1.0)
            continue
        if op in ("min", "max"):
            fill = _identity_for(op, v.dtype)
            v = jnp.where(m, v, fill)
        else:
            v = jnp.where(_bmask(m, v), v, jnp.zeros_like(v) if op == "sum" else jnp.ones_like(v))
        cols[out] = _SEG_OPS[op](v, seg, num_segments=nsegments)

    if sortfree:
        return sortfree_result(t, keys, rep, out_valid, unplaced, bound,
                               cols)
    return Table(poison_overflow(cols, overflow_ok), out_valid)


def _group_agg_fused(st: Table, seg: jax.Array, m: jax.Array,
                     num_segments: int, fused_aggs, backend: str,
                     shard_route=None, layout: str = "sorted",
                     sortfree_keys=None, seg_map=None):
    """Serve sum/count/min/max/mean/argmin/argmax GroupAgg ops from ONE
    fused segment-aggregate pass: each distinct value (or arg-extremum
    key) column is one kernel column; all requested moments come back
    together, so e.g. (sum, count, mean, min) over one column costs a
    single HBM traversal.  Arg-extremum ops additionally request the
    kernel's index moment — the first-attaining row index arrives as
    output rows 4/5, and the payload is one num_segments-sized take (no
    row-capacity-sized gather).  ``num_segments`` is the static segment
    range — the dense group bound (+ overflow slot) when declared, the
    row capacity otherwise — and sizes the (C, R, num_segments) moment
    tensor.  ``shard_route`` = (mesh, axis): the pass runs per row shard
    with a cross-device moment merge, arg-extremum rows merged as
    lexicographic (key, global_row) collectives and payloads gathered
    shard-locally (launch/sharded_agg.py).

    ``layout='unsorted'`` runs the same pass on hash-slotted (unsorted)
    segment ids — the sort-free route.  ``sortfree_keys`` (the group-key
    names, sharded sort-free only) makes the launcher slot each shard's
    rows itself and merge key-aligned; ``seg`` is then unused and the
    return value becomes ``(cols, (rep_rows, out_valid, unplaced))`` so
    the caller can recover representatives/validity without global
    segment ids.  ``seg_map``: ``seg`` holds shard-local segment ids
    (``launch.sharded_agg.shard_local_segments``)."""
    from repro.core.executors import _index_row_to_pick
    from repro.kernels.segment_agg import (ARGMAX_ROW, ARGMIN_ROW,
                                           fused_segment_agg)

    cap = st.capacity
    value_cols = list(dict.fromkeys(
        (col[0] if op in _ARG_OPS else col)
        for _, op, col in fused_aggs if col is not None))
    if not value_cols:        # count-only: any column works, mask does the job
        vals = jnp.zeros((cap, 1), jnp.float32)
        col_idx = {}
    else:
        vals = jnp.stack([st.columns[c].astype(jnp.float32)
                          for c in value_cols], axis=1)
        col_idx = {c: i for i, c in enumerate(value_cols)}
    moments = [set() for _ in range(max(1, len(value_cols)))]
    for _, op, col in fused_aggs:
        if op in _ARG_OPS:
            moments[col_idx[col[0]]].update(
                ("min", "argmin_first") if op == "argmin"
                else ("max", "argmax_first"))
            continue
        i = col_idx.get(col, 0)   # count (col=None) rides on column 0
        moments[i].update({"mean": ("sum", "count"),
                           "count": ("count",)}.get(op, (op,)))
    kernel_moments = tuple(tuple(sorted(ms)) for ms in moments)

    # sharded route: arg payloads are gathered shard-locally inside the
    # all-reduce, so hand the payload columns to the launcher
    payload_specs = []
    payload_slot = {}
    if shard_route is not None:
        for name, op, col in fused_aggs:
            if op in _ARG_OPS:
                payload_slot[name] = len(payload_specs)
                payload_specs.append((col_idx[col[0]], op == "argmin",
                                      (st.columns[col[1]],)))

    # sorted layout: segment_ids_for sorted the rows, so the band-pruned
    # kernel may assume the sorted-segs precondition; unsorted layout
    # (sort-free) disables pruning and the check outright
    payload_picks = ()
    sortfree_extras = None
    if sortfree_keys is not None:
        from repro.launch.sharded_agg import sharded_sortfree_segment_agg
        from .keyslot import key_words_for
        kw = key_words_for(st.columns[k] for k in sortfree_keys)
        bucket = num_segments - 1
        fused, payload_picks, rep, occupied, unplaced = \
            sharded_sortfree_segment_agg(
                vals, kw, m[:, None], m, num_segments, bucket,
                mesh=shard_route[0], axis=shard_route[1], backend=backend,
                moments=kernel_moments, payloads=tuple(payload_specs))
        sortfree_extras = (rep, occupied, unplaced)
    elif shard_route is not None:
        from repro.launch.sharded_agg import sharded_fused_segment_agg
        res = sharded_fused_segment_agg(
            vals, seg.astype(jnp.int32), m[:, None], num_segments,
            mesh=shard_route[0], axis=shard_route[1], backend=backend,
            moments=kernel_moments, assume_sorted=True,
            payloads=tuple(payload_specs), layout=layout, seg_map=seg_map)
        fused, payload_picks = res if payload_specs else (res, ())
    else:
        fused = fused_segment_agg(vals, seg.astype(jnp.int32), m[:, None],
                                  num_segments, backend=backend,
                                  moments=kernel_moments,
                                  assume_sorted=True, layout=layout)

    out: dict[str, jax.Array] = {}
    count = fused[0, 1]
    for name, op, col in fused_aggs:
        if op == "count":
            out[name] = count.astype(
                jnp.int64 if jax.config.jax_enable_x64 else jnp.int32)
            continue
        if op in _ARG_OPS:
            minimize = op == "argmin"
            i = col_idx[col[0]]
            pv = st.columns[col[1]]
            pick = _index_row_to_pick(
                fused[i, ARGMIN_ROW if minimize else ARGMAX_ROW], cap,
                tie_first=True)
            got = (pick >= 0) & (pick < cap)
            if name in payload_slot:
                pv_pick = payload_picks[payload_slot[name]][0].astype(
                    pv.dtype)
            else:
                pv_pick = jnp.take(pv, jnp.clip(pick, 0, cap - 1))
            out[name] = jnp.where(got, pv_pick, jnp.zeros((), pv.dtype))
            continue
        i = col_idx[col]
        d = st.columns[col].dtype
        if op == "sum":
            out[name] = fused[i, 0].astype(d)
        elif op == "mean":
            out[name] = fused[i, 0] / jnp.maximum(fused[i, 1], 1.0)
        elif op == "min":
            out[name] = fused[i, 2].astype(d)
        else:  # max
            out[name] = fused[i, 3].astype(d)
    if sortfree_extras is not None:
        return out, sortfree_extras
    return out


def _identity_for(op: str, dtype) -> jax.Array:
    if op == "min":
        return jnp.array(jnp.inf, dtype) if jnp.issubdtype(dtype, jnp.floating) \
            else jnp.array(jnp.iinfo(dtype).max, dtype)
    if op == "max":
        return jnp.array(-jnp.inf, dtype) if jnp.issubdtype(dtype, jnp.floating) \
            else jnp.array(jnp.iinfo(dtype).min, dtype)
    raise ValueError(op)
