"""Executors: the faithful cursor baselines and the Aggify execution paths.

Baselines (paper §2.3 — what Aggify eliminates):
  * ``run_cursor(interpreted=True)``  — host-driven row-at-a-time evaluation
    (the client/JDBC or interpreted T-SQL model: per-row dispatch overhead).
  * ``run_cursor()``                  — in-engine sequential loop: the cursor
    query is **materialized** (temp table barrier), then folded row-by-row
    with ``lax.scan``.

Aggify paths (§5/§6 + our beyond-paper parallel modes):
  * ``mode='stream'``     — Eq. 6 streaming aggregate (sequential, pipelined,
                            no temp table).  Always available.
  * ``mode='chunked'``    — Merge-parallel partial aggregation (synthesized
                            merge; see recognize.py).
  * ``mode='recognized'`` — fully set-oriented closed form (no scan at all).
  * ``mode='fused'``      — grouped: recognized updates lowered onto the
                            fused Pallas segment-aggregate kernel
                            (kernels/segment_agg.py) — one VMEM-resident
                            pass computes every sum/count/min/max moment
                            AND the arg-extremum attaining-row index (the
                            kernel's index moment, tie-ordered) for every
                            recognized column; payload selection is then a
                            num_segments-sized take, and the remaining
                            update kinds (last/prod, wide-dtype fields)
                            stay on jnp segment ops in the same XLA
                            program.  Ungrouped, the closed form is
                            already one fused pass, so 'fused' coincides
                            with 'recognized'.
  * ``mode='auto'``       — fused > recognized > chunked > stream.

Grouped invocation (``AggCall.group_keys``) decorrelates per-group loops
(the paper's Q2/minCostSupp-per-part pattern) into a single pass — fused
(Pallas kernel), segment-vectorized (recognized), or one segmented scan
(generic).  Kernel backend selection: compiled on TPU, ``jax.ops.segment_*``
fallback on CPU/GPU; ``REPRO_SEGAGG_BACKEND`` ∈ {pallas, interpret, jnp}
overrides, and the legacy ``REPRO_SEGAGG_PALLAS=1`` forces the kernel
(interpret mode off-TPU).
"""
from __future__ import annotations

from typing import Any, Mapping, NamedTuple, Optional

import jax
import jax.numpy as jnp
from jax import lax

from repro.relational import engine as _engine
from repro.relational.plan import AggCall
from repro.relational.table import Table

from . import recognize as _recognize
from .aggregate import fold_moments  # noqa: F401  (public re-export: the
#   incremental serving layer folds micro-batch moments through the same
#   door the grouped executors launch them from)
from .aggify import CustomAggregate, RewrittenProgram, aggify, exec_stmts
from .loop_ir import (Assign, Col, CursorLoop, Program, Var, assigned_vars,
                      eval_expr, expr_cols)


# ---------------------------------------------------------------------------
# Environment setup
# ---------------------------------------------------------------------------


def _default_for(prog, name):
    dt = prog.var_dtypes.get(name, jnp.float32)
    return jnp.zeros((), dtype=dt)


def _default_missing_fields(agg, env, outer_vals, var_dtypes) -> None:
    """Fill ``outer_vals`` defaults for aggregate fields absent from the
    caller environment.  Dtype resolution (shared by the grouped and
    ungrouped paths so they cannot diverge): the explicit ``var_dtypes``
    param wins, then the mapping the aggregate carried from
    ``Program.var_dtypes`` (the engine's plan-execution path has no way
    to pass the param), else float32."""
    dtypes = var_dtypes if var_dtypes is not None \
        else getattr(agg, "var_dtypes", None)
    for f in agg.fields:
        if f in env:
            outer_vals.setdefault(f, env[f])
        else:
            dt = (dtypes or {}).get(f, jnp.float32)
            outer_vals.setdefault(f, jnp.zeros((), dtype=dt))


def build_env(prog, catalog, params: Optional[Mapping[str, Any]] = None) -> dict:
    env: dict[str, Any] = {}
    for p in prog.params:
        if params is None or p not in params:
            raise ValueError(f"missing parameter {p!r}")
        env[p] = jnp.asarray(params[p])
    for tv, (dtypes, cap) in prog.local_tables.items():
        bufs = tuple(jnp.zeros((cap,), dtype=d) for d in dtypes)
        env[tv] = (bufs, jnp.array(0, jnp.int32))
    env = exec_stmts(prog.pre, env)
    return env


# ---------------------------------------------------------------------------
# Cursor baselines
# ---------------------------------------------------------------------------


def run_cursor(prog: Program, catalog, params=None, interpreted: bool = False):
    """Reference semantics: materialize Q, iterate Δ row-by-row."""
    env = build_env(prog, catalog, params)
    loop = prog.loop
    assert isinstance(loop, CursorLoop)
    t = _engine.execute(loop.query, catalog, env)
    t = t.compress().materialize()       # the temp-table barrier (§2.3)

    rows = {v: t.columns[c] for v, c in loop.fetch}
    valid = t.mask()
    state_vars = sorted(assigned_vars(loop.body))
    state0 = {v: env[v] if v in env else _default_for(prog, v)
              for v in state_vars}

    if interpreted:
        import numpy as np
        n = int(np.asarray(jnp.sum(valid)))
        st = dict(state0)
        for i in range(n):
            e = dict(env); e.update(st)
            e.update({v: jax.tree.map(lambda a: a[i], c)
                      for v, c in rows.items()})
            e2 = exec_stmts(loop.body, e)
            st = {v: e2[v] for v in state_vars}
        env.update(st)
    else:
        def step(state, xs):
            row, ok = xs
            e = dict(env); e.update(state); e.update(row)
            e2 = exec_stmts(loop.body, dict(e))
            new = {v: e2[v] for v in state_vars}
            new = jax.tree.map(lambda a, b: jnp.where(ok, a, b), new, state)
            return new, None

        final, _ = lax.scan(step, state0, (rows, valid))
        env.update(final)

    env = exec_stmts(prog.post, env)
    return {r: env[r] for r in prog.returns}


# ---------------------------------------------------------------------------
# Rewritten execution
# ---------------------------------------------------------------------------


def run_rewritten(rp: RewrittenProgram, catalog, params=None,
                  mode: Optional[str] = None, deferred_init: bool = False,
                  num_chunks: int = 8):
    env: dict[str, Any] = {}
    for p in rp.params:
        if params is None or p not in params:
            raise ValueError(f"missing parameter {p!r}")
        env[p] = jnp.asarray(params[p])
    agg = rp.aggregate
    for tv, (dtypes, cap) in agg.local_tables.items():
        bufs = tuple(jnp.zeros((cap,), dtype=d) for d in dtypes)
        env[tv] = (bufs, jnp.array(0, jnp.int32))
    env = exec_stmts(rp.pre, env)

    call = rp.agg_call if mode is None else AggCall(
        rp.agg_call.child, rp.agg_call.aggregate, rp.agg_call.param_binding,
        rp.agg_call.ordered, rp.agg_call.sort_keys, rp.agg_call.sort_desc,
        rp.agg_call.group_keys, mode, rp.agg_call.max_groups)
    vals = agg_call_values(call, catalog, env, deferred_init=deferred_init,
                           num_chunks=num_chunks, var_dtypes=rp.var_dtypes)
    env.update(vals)
    env = exec_stmts(rp.post, env)
    return {r: env[r] for r in rp.returns}


def run_aggify(prog: Program, catalog, params=None, mode: str = "auto",
               deferred_init: bool = False, num_chunks: int = 8):
    """Convenience: Algorithm 1 + execute."""
    rp = aggify(prog, mode=mode)
    return run_rewritten(rp, catalog, params, deferred_init=deferred_init,
                         num_chunks=num_chunks)


# ---------------------------------------------------------------------------
# AggCall evaluation
# ---------------------------------------------------------------------------


def fused_eligible(agg: CustomAggregate) -> bool:
    """True when the accumulator decomposes into moments the fused Pallas
    segment-aggregate kernel computes: at least one recognized sum/min/max
    update (counts are sums of 1; means are sum/count) or an argmin/argmax
    group, whose key extremum AND attaining-row index both come from the
    kernel (the index moment) — payload selection is then a single
    num_segments-sized take in the same XLA program."""
    return (agg.recognized is not None and not agg.local_tables
            and any(u.kind in ("sum", "min", "max", "arg_group")
                    for u in agg.recognized))


def _resolve_mode(call: AggCall, agg: CustomAggregate,
                  deferred_init: bool) -> str:
    mode = call.mode
    if deferred_init:
        # deferred V_init (paper §5.2) only exists on the streaming fold;
        # an explicit request for a parallel/closed-form mode cannot be
        # honored, so refuse it rather than silently running 'stream'
        if mode not in ("auto", "stream"):
            raise ValueError(
                f"deferred_init=True requires streaming execution; "
                f"incompatible with explicit mode={mode!r}")
        return "stream"
    if mode == "auto":
        if agg.recognized is not None and not agg.local_tables:
            return "recognized"
        if agg.mergeable:
            return "chunked"
        return "stream"
    if mode == "fused":
        # ungrouped: the closed form already is one fused pass
        if agg.recognized is None:
            raise ValueError(f"aggregate {agg.name!r} not recognized; cannot "
                             "run in fused mode")
        return "recognized"
    if mode == "recognized" and agg.recognized is None:
        raise ValueError(f"aggregate {agg.name!r} not recognized; cannot "
                         "run in recognized mode")
    if mode == "chunked" and not agg.mergeable:
        raise ValueError(f"aggregate {agg.name!r} has no merge")
    return mode


def _agg_call_needed(call: AggCall) -> tuple[str, ...]:
    """Columns an AggCall reads from its child: group/sort keys plus
    every Col its parameter bindings reference — the ``needed`` set the
    whole-plan fusion pass (relational/fuse.py) materializes."""
    need = list(call.group_keys) + list(call.sort_keys)
    for _name, e in call.param_binding:
        need.extend(sorted(expr_cols(e)))
    return tuple(need)


def agg_call_values(call: AggCall, catalog, env, deferred_init=False,
                    num_chunks: int = 8, var_dtypes=None) -> dict[str, Any]:
    """Evaluate 𝒢_{AggΔ}(Q) (ungrouped) → {V_term var: value}."""
    if call.group_keys:
        raise ValueError("grouped AggCall: use execute_agg_call / engine")
    agg: CustomAggregate = call.aggregate
    t = _engine.execute_for_agg(call.child, catalog, env,
                                _agg_call_needed(call))
    if call.ordered:
        t = t.sort_by(call.sort_keys, call.sort_desc)

    rows: dict[str, jax.Array] = {}
    outer_vals: dict[str, Any] = {}
    for name, e in call.param_binding:
        if isinstance(e, Col):
            rows[name] = t.columns[e.name]
        else:
            outer_vals[name] = eval_expr(e, env)
    _default_missing_fields(agg, env, outer_vals, var_dtypes)

    valid = t.mask()
    mode = _resolve_mode(call, agg, deferred_init)

    if mode == "recognized":
        col_env = dict(outer_vals)
        col_env.update(rows)
        outer_state = {f: jnp.asarray(outer_vals[f]) for f in agg.fields}
        out = _recognize.vectorized_eval(agg.recognized, col_env, valid,
                                         outer_state)
        return {v: out.get(v, outer_state[v]) for v in agg.terminate_vars}

    jagg = agg.as_jax_aggregate(outer_vals, deferred_init=deferred_init)
    from .aggregate import chunked, streaming
    if mode == "chunked":
        res = chunked(jagg, rows, valid, num_chunks=num_chunks)
    else:
        res = streaming(jagg, rows, valid)
    return dict(zip(agg.terminate_vars, res))


def execute_agg_call(call: AggCall, catalog, env,
                     var_dtypes=None) -> Table:
    """Engine entry point: returns a Table (1 row, or one row per group).
    ``var_dtypes`` (Program.var_dtypes) resolves the dtype of aggregate
    fields absent from ``env`` — without it they default to float32."""
    if call.group_keys:
        return grouped_agg_call(call, catalog, env, var_dtypes=var_dtypes)
    vals = agg_call_values(call, catalog, env, var_dtypes=var_dtypes)
    cols = {}
    for k, v in vals.items():
        a = jnp.asarray(v)
        cols[k] = a[None] if a.ndim == 0 else a[None, ...]
    return Table(cols, jnp.ones(1, dtype=bool))


# ---------------------------------------------------------------------------
# Grouped invocation (decorrelation)
# ---------------------------------------------------------------------------


#: recognized update kinds whose merge algebra is commutative — the
#: sort-free grouped route only fires when every update is one of these
#: ('last' is positional over the *iteration* order, so it stays sorted)
_ORDER_INSENSITIVE_KINDS = ("sum", "prod", "min", "max", "arg_group")
#: recognized update kinds whose result depends on the order of a
#: group's rows: the attaining row of a tie, the last row
_ORDER_SENSITIVE_KINDS = ("arg_group", "last")


def _sortfree_eligible(call: AggCall, agg: CustomAggregate, mode: str,
                       bound, rows: int, nsegments: int) -> bool:
    """True when the grouped call may skip the group sort entirely: a
    dense bound is declared (the hash slot table is bucket-sized), the
    call is order-insensitive (no Eq.-6 ordering, no sort keys), the
    physical mode is set-oriented (the segmented scan IS sequential
    semantics), every recognized update folds with a commutative merge,
    and — in fused mode — the kernel's unsorted grid over ``rows`` rows
    per launch does not dwarf the sorted one.  The grid rule reads the
    mode, not the parameters' dtypes, so a plan's route is known before
    its first request."""
    from repro.relational.engine import kernel_grid_allows_sortfree
    from repro.relational.keyslot import sortfree_enabled
    return (bound is not None and sortfree_enabled()
            and not call.ordered and not call.sort_keys
            and mode in ("fused", "recognized")
            and agg.recognized is not None
            and all(u.kind in _ORDER_INSENSITIVE_KINDS
                    for u in agg.recognized)
            and (mode != "fused" or kernel_grid_allows_sortfree(
                _segagg_backend(), rows, nsegments)))


class _CallSetup(NamedTuple):
    mode: str
    nsegments: int
    bound: Optional[int]
    shard_route: Optional[tuple]
    cached: bool
    rows: dict
    outer_vals: dict
    updates_split: Optional[tuple]
    route: "GroupRoute"


def _grouped_call_setup(call: AggCall, t: Table, env,
                        var_dtypes=None,
                        cached: Optional[bool] = None) -> _CallSetup:
    """Every static decision of ``grouped_agg_call`` over its input table
    ``t``: segment range, row split, parameter bindings, and the route.
    ``env`` None (the parameters are not known yet) leaves the kernel
    bit of the route undecided."""
    from repro.launch.sharded_agg import (launch_rows, shard_local_merge,
                                          table_row_split)
    from repro.relational.engine import GroupRoute
    from repro.relational.group_bound import resolve_group_bound
    from repro.relational.keyslot import provided_slots
    agg: CustomAggregate = call.aggregate
    # row-split input (Table.shard_rows): the fused path runs the kernel
    # per shard and all-reduces moments; read BEFORE the sort
    shard_route = table_row_split(t)
    # dense segment range: AggCall-declared max_groups beats the table
    # hint; every segment tensor below (and the kernel / all-reduce
    # payload) is sized by it instead of the row capacity
    declared = call.max_groups if call.max_groups is not None \
        else t.group_bound
    nsegments, bound = resolve_group_bound(declared, t.capacity)
    # a provide_slots scope carrying this call's slot table beats the
    # per-shard slotting launcher: the cached assignment is global and
    # stable across calls, so each shard aggregates onto it directly
    if cached is None:
        cached = (bound is not None and provided_slots(
            tuple(call.group_keys), bound) is not None)
    cap = t.capacity
    mode = _resolve_grouped_mode(call, agg)

    # bind params against the unsorted table first: routing only consults
    # dtypes, and the sort-free route consumes these bindings as-is
    rows: dict[str, jax.Array] = {}
    outer_vals: dict[str, Any] = {}
    for name, e in call.param_binding:
        if isinstance(e, Col):
            rows[name] = t.columns[e.name]
        elif env is not None:
            outer_vals[name] = eval_expr(e, env)
    _default_missing_fields(agg, env or {}, outer_vals, var_dtypes)

    launch = launch_rows(cap, shard_route)
    sortfree = _sortfree_eligible(call, agg, mode, bound, launch,
                                  nsegments)
    kernel_updates = None
    if env is not None and mode == "fused":
        col_env = dict(outer_vals)
        col_env.update(rows)
        kernel_updates, rest = _split_kernel_updates(agg, outer_vals,
                                                     col_env)
    updates_split = None
    if sortfree and shard_route is not None and not cached:
        # sharded sort-free assigns slots per shard inside the launcher —
        # only viable when the WHOLE aggregate lowers to the kernel pass
        # (jnp-routed leftovers would need global segment ids), arg
        # updates included: past the f32-exact index ceiling their
        # legacy select tail needs global ids too
        from repro.kernels.segment_agg import index_moment_ok
        if (kernel_updates is None or rest or not kernel_updates
                or (any(u.kind == "arg_group" for u in kernel_updates)
                    and not index_moment_ok(cap))):
            sortfree = False
        else:
            updates_split = (kernel_updates, rest)
    from repro.kernels.segment_agg import resolve_backend
    kernel = None if env is None else (
        bool(kernel_updates)
        and resolve_backend(_segagg_backend()) != "jnp")
    # a row-split input sorts shard by shard when the segments need no
    # order across shards: no Eq.-6 ordering or sort keys, and a mode
    # whose updates merge by group (the segmented scan walks rows)
    merge = None
    if (not sortfree and not call.ordered and not call.sort_keys
            and mode in ("fused", "recognized")):
        merge = shard_local_merge(t, call.group_keys, bound, shard_route)
    return _CallSetup(mode, nsegments, bound, shard_route, cached, rows,
                      outer_vals, updates_split,
                      GroupRoute(sortfree, kernel, launch, nsegments,
                                 merge))


def grouped_call_route(call: AggCall, t: Table, env=None, *,
                       cached: Optional[bool] = None) -> "GroupRoute":
    """The route a grouped AggCall takes over its input table ``t``
    (``relational.engine.grouped_route`` is the entry point)."""
    return _grouped_call_setup(call, t, env, cached=cached).route


def grouped_agg_call(call: AggCall, catalog, env,
                     var_dtypes=None) -> Table:
    agg: CustomAggregate = call.aggregate
    t = _engine.execute_for_agg(call.child, catalog, env,
                                _agg_call_needed(call))
    from repro.relational.engine import segment_ids_for
    from repro.relational.group_bound import (check_group_overflow,
                                              poison_overflow)
    from repro.relational.keyslot import (overflow_extended,
                                          slot_segment_ids,
                                          sortfree_result)
    (mode, nsegments, bound, shard_route, cached, rows, outer_vals,
     updates_split, route) = _grouped_call_setup(call, t, env, var_dtypes)
    sortfree = route.sortfree
    cap = t.capacity

    cols: dict[str, jax.Array] = {}
    if sortfree:
        st, m = t, t.mask()
        if shard_route is not None and not cached:
            out, (rep, out_valid, unplaced) = _grouped_fused(
                agg, rows, outer_vals, m, None, nsegments,
                backend=_segagg_backend(),
                require_kernel=call.mode == "fused",
                shard_route=shard_route,
                sortfree_keys=tuple(call.group_keys), table=st,
                updates_split=updates_split)
        else:
            seg, owner, occupied, unplaced = slot_segment_ids(
                t, call.group_keys, bound)
            rep, out_valid = overflow_extended(owner, occupied, cap)
            if mode == "fused":
                out = _grouped_fused(agg, rows, outer_vals, m, seg,
                                     nsegments, backend=_segagg_backend(),
                                     require_kernel=call.mode == "fused",
                                     shard_route=shard_route,
                                     layout="unsorted")
            else:
                out = _grouped_recognized(agg, rows, outer_vals, m, seg,
                                          nsegments)
        return sortfree_result(st, call.group_keys, rep, out_valid,
                               unplaced, bound,
                               {v: out[v] for v in agg.terminate_vars})

    seg_map = None
    if route.merge is not None:
        # row-split input: one sort per shard by the group keys, partial
        # groups merged across devices by key
        from repro.launch.sharded_agg import shard_local_segments
        ss = shard_local_segments(
            t, call.group_keys, nsegments, bound, route.merge, *shard_route,
            stable=any(u.kind in _ORDER_SENSITIVE_KINDS
                       for u in agg.recognized))
        st, seg, seg_map, m = ss.table, ss.seg, ss.seg_map, ss.table.mask()
        overflow_ok = ss.guard()
        out_valid = jnp.arange(nsegments) < ss.nseg
        for name, e in call.param_binding:
            if isinstance(e, Col):
                rows[name] = st.columns[e.name]
        cols.update(ss.keys)
    else:
        sort_keys = tuple(call.group_keys) + tuple(call.sort_keys)
        sort_desc = (False,) * len(call.group_keys) + tuple(
            call.sort_desc or (False,) * len(call.sort_keys))
        st, seg, starts = segment_ids_for(
            t.sort_by(sort_keys, sort_desc), call.group_keys,
            num_segments=nsegments)
        # note: sort_by in segment_ids_for re-sorts by group keys only
        # (stable), preserving the intra-group order established above.
        m = st.mask()
        nseg = jnp.sum(starts.astype(jnp.int32))
        overflow_ok = check_group_overflow(nseg, bound)
        out_valid = jnp.arange(nsegments) < nseg

        # re-bind fetch-derived params against the SORTED rows
        for name, e in call.param_binding:
            if isinstance(e, Col):
                rows[name] = st.columns[e.name]

        first_idx = jnp.where(starts, jnp.arange(cap), cap)
        first_of_seg = jax.ops.segment_min(first_idx, seg,
                                           num_segments=nsegments)
        safe_first = jnp.clip(first_of_seg, 0, cap - 1)
        for k in call.group_keys:
            cols[k] = jnp.take(st.columns[k], safe_first)

    if mode == "fused":
        out = _grouped_fused(agg, rows, outer_vals, m, seg, nsegments,
                             backend=_segagg_backend(),
                             require_kernel=call.mode == "fused",
                             shard_route=shard_route, seg_map=seg_map)
    elif mode == "recognized":
        if seg_map is not None:
            from repro.launch.sharded_agg import global_segment_ids
            seg = global_segment_ids(seg, seg_map, nsegments, *shard_route)
        out = _grouped_recognized(agg, rows, outer_vals, m, seg, nsegments)
    else:
        out = _grouped_scan(agg, rows, outer_vals, m, starts, seg,
                            nsegments)
    for v in agg.terminate_vars:
        cols[v] = out[v]
    return Table(poison_overflow(cols, overflow_ok), out_valid)


def _resolve_grouped_mode(call: AggCall, agg: CustomAggregate) -> str:
    """Grouped physical-mode selection: fused > recognized > scan.
    'stream' and 'chunked' both lower to the generic segmented scan (the
    per-group sequential semantics; chunk-parallelism within a segment is
    an open item)."""
    mode = call.mode
    recognized = agg.recognized is not None and not agg.local_tables
    if mode == "auto":
        if fused_eligible(agg):
            return "fused"
        return "recognized" if recognized else "scan"
    if mode == "fused":
        if not fused_eligible(agg):
            raise ValueError(
                f"aggregate {agg.name!r} has no fused-eligible recognized "
                "updates (sum/min/max/argmin/argmax); cannot run in fused "
                "mode")
        return "fused"
    if mode == "recognized":
        if not recognized:
            raise ValueError(f"aggregate {agg.name!r} not recognized; cannot "
                             "run in recognized mode")
        return "recognized"
    if mode == "chunked" and not agg.mergeable:
        raise ValueError(f"aggregate {agg.name!r} has no merge")
    return "scan"


def _segagg_backend() -> str:
    """Kernel backend for the fused grouped path: compiled on TPU, pure-JAX
    segment ops on CPU/GPU (the interpreter loop is test-only).  A
    thread-local ``reliability.degrade.force_backend`` scope wins over
    everything — the serving circuit breaker traces its degraded
    executable under it.  Env overrides: REPRO_SEGAGG_BACKEND, or legacy
    REPRO_SEGAGG_PALLAS=1."""
    from repro.configs import flags
    from repro.reliability.degrade import forced_backend
    forced = forced_backend()
    if forced is not None:
        return forced
    env = flags.choice("REPRO_SEGAGG_BACKEND", ("pallas", "interpret", "jnp"))
    if env is not None:
        return env
    on_tpu = jax.default_backend() == "tpu"
    if flags.value("REPRO_SEGAGG_PALLAS") == "1":
        return "pallas" if on_tpu else "interpret"
    return "pallas" if on_tpu else "jnp"


def _f32_exact_key_dtype(dt) -> bool:
    """True when every value of ``dt`` survives the cast to the kernel's
    f32 accumulator exactly: ≤32-bit floats (f16/bf16 embed exactly),
    bools, and ≤16-bit ints.  Wide ints and float64 can collide after the
    cast, which would mis-pick the attaining row of an arg-extremum — key
    expressions of those dtypes route to the exact jnp path (mirroring
    the f32-exactness gating of the count/mean built-ins)."""
    d = jnp.dtype(dt)
    if jnp.issubdtype(d, jnp.floating):
        return d.itemsize <= 4
    if d == jnp.bool_:
        return True
    if jnp.issubdtype(d, jnp.integer):
        return d.itemsize <= 2
    return False


def _split_kernel_updates(agg, outer_vals, col_env):
    """Partition the recognized updates into (kernel_updates, rest): the
    fused kernel accumulates in f32, so only sum/min/max/arg_group
    updates over ≤32-bit floating fields — with f32-exactly-embeddable
    arg keys — take the kernel pass; everything else stays on the jnp
    segment ops (in the same XLA program)."""
    kernel_updates = []
    rest = []
    for u in agg.recognized:
        d = jnp.asarray(outer_vals[u.fields[0]]).dtype
        # the kernel accumulates in f32: float64 fields would silently
        # lose precision, so they stay on the jnp path in their own dtype
        ok = (u.kind in ("sum", "min", "max", "arg_group")
              and jnp.issubdtype(d, jnp.floating)
              and jnp.dtype(d).itemsize <= 4)
        if ok and u.kind == "arg_group":
            # ... and so would wide-int/f64 KEY EXPRESSIONS (not just
            # fields): distinct keys that collide in f32 would mis-pick
            # the attaining row, so those route to the exact path too
            # (eval_shape: the dtype probe must not evaluate the N-row
            # expression a second time under eager execution; the
            # environment is an argument, so abstract columns work too)
            ok = _f32_exact_key_dtype(
                jax.eval_shape(lambda env, u=u: jnp.asarray(
                    eval_expr(u.exprs[0], env)), col_env).dtype)
        (kernel_updates if ok else rest).append(u)
    return kernel_updates, rest


def _grouped_fused(agg, rows, outer_vals, valid, seg, num_segments, backend="auto",
                   require_kernel=False, shard_route=None,
                   layout="sorted", sortfree_keys=None, table=None,
                   updates_split=None, seg_map=None):
    """Fused grouped aggregation: every recognized sum/min/max/arg-extremum
    update over a ≤32-bit floating field is batched into ONE fused
    segment-aggregate pass (each column carries its own guard mask, so
    differently-guarded updates still share the traversal); remaining
    updates (prod/last, float64/integer fields, wide-int/f64 arg-extremum
    keys) run on the jnp segment path in the same XLA program.

    Arg-extremum updates additionally request the kernel's INDEX MOMENT:
    the attaining row index comes back as output rows 4/5 with the loop's
    tie order, so the whole update is consumed with a num_segments-sized
    payload take — no hit-detection equality scan, no full-row candidate
    reduce, no row-capacity-sized gather (``_arg_select_from_index``).

    ``require_kernel`` (an explicit ``mode='fused'`` request) raises
    instead of silently running a kernel-free pass when every update is
    dtype-routed to jnp.  ``shard_route`` = (mesh, axis) routes the kernel
    pass through ``launch.sharded_agg.sharded_fused_segment_agg`` — one
    kernel launch per row shard, moments all-reduced over the mesh axis,
    arg-extremum payloads gathered shard-locally and merged as
    O(num_segments) collectives (never O(rows)).

    SORT-FREE variants: ``layout='unsorted'`` runs the identical pass on
    hash-slotted segment ids (no pre-sort happened).  ``sortfree_keys``
    (+ ``table``, sharded only) hands slotting to the launcher itself —
    each shard slots its own rows and the merge is key-aligned; ``seg``
    is unused and the return value becomes ``(out, (rep_rows, out_valid,
    unplaced))`` so the caller recovers representatives and validity
    without global segment ids.  ``seg_map``: ``seg`` holds shard-local
    segment ids (``launch.sharded_agg.shard_local_segments``), which the
    launcher maps onto global ones; the paths that need one global id
    per row derive them once."""
    from repro.kernels.segment_agg import (ARGMAX_ROW, ARGMIN_ROW,
                                           fused_segment_agg,
                                           index_moment_ok)

    col_env = dict(outer_vals)
    col_env.update(rows)
    n = valid.shape[0]

    def row_seg():
        if seg_map is None:
            return seg
        from repro.launch.sharded_agg import global_segment_ids
        return global_segment_ids(seg, seg_map, num_segments, *shard_route)
    # f32 row indices are exact below 2^24 PADDED rows (the same gate the
    # kernel validates); beyond that the arg-extremum keeps the kernel
    # key extremum but falls back to the legacy jnp pick
    use_index = index_moment_ok(n)

    kernel_updates, rest = (updates_split if updates_split is not None
                            else _split_kernel_updates(agg, outer_vals,
                                                       col_env))
    if require_kernel and not kernel_updates:
        raise ValueError(
            f"aggregate {agg.name!r}: no recognized update targets a ≤32-bit "
            "floating field (the kernel accumulates in f32), so mode='fused' "
            "would run no kernel work — use mode='recognized' or 'auto'")

    out: dict[str, jax.Array] = {}
    if kernel_updates:
        cols = []
        masks = []
        moments: list[set] = []    # per kernel column
        col_of: dict = {}          # (expr, guard[, tie]) -> column index
        upd_col = []
        upd_mname = []             # index-moment name per update (or None)
        for u in kernel_updates:
            ck = (u.exprs[0], u.guard)
            mname = None
            if u.kind == "arg_group" and use_index:
                minimize = u.op in ("<", "<=")
                tie_first = u.op in ("<", ">")
                mname = (("argmin" if minimize else "argmax")
                         + ("_first" if tie_first else "_last"))
                conflict = (("argmin" if minimize else "argmax")
                            + ("_last" if tie_first else "_first"))
                if ck in col_of and conflict in moments[col_of[ck]]:
                    # one index row per extremum direction: an update with
                    # the opposite tie order gets its own column
                    ck = ck + (mname,)
            if ck not in col_of:    # min+max over one column share a pass
                g = valid
                if u.guard is not None:
                    g = g & jnp.asarray(eval_expr(u.guard, col_env), bool)
                e = jnp.broadcast_to(
                    jnp.asarray(eval_expr(u.exprs[0], col_env), jnp.float32),
                    (n,))
                col_of[ck] = len(cols)
                cols.append(e)
                masks.append(g)
                moments.append(set())
            c = col_of[ck]
            upd_col.append(c)
            upd_mname.append(mname)
            if u.kind == "arg_group":
                moments[c].add("min" if u.op in ("<", "<=") else "max")
                if mname is not None:
                    moments[c].add(mname)
            else:
                moments[c].add(u.kind)
        kernel_moments = tuple(tuple(sorted(ms)) for ms in moments)

        # sharded route: payload candidates are gathered SHARD-LOCALLY and
        # merged inside the all-reduce, so evaluate them up front
        payload_specs = []
        payload_slot = {}          # update position -> slot in the result
        if shard_route is not None:
            for j, (u, c, mname) in enumerate(zip(kernel_updates, upd_col,
                                                  upd_mname)):
                if mname is None:
                    continue
                pvals = tuple(
                    jnp.broadcast_to(
                        jnp.asarray(eval_expr(pe, col_env),
                                    jnp.asarray(outer_vals[f]).dtype), (n,))
                    for f, pe in zip(u.fields[1:], u.exprs[1:]))
                payload_slot[j] = len(payload_specs)
                payload_specs.append((c, u.op in ("<", "<="), pvals))

        # sorted layout: the grouped sort established the sorted-segs
        # precondition by construction, so the band-pruned kernel skips
        # its guard; unsorted layout (sort-free) never had an order
        payload_picks = ()
        sortfree_extras = None
        if sortfree_keys is not None:
            from repro.launch.sharded_agg import \
                sharded_sortfree_segment_agg
            from repro.relational.keyslot import key_words_for
            kw = key_words_for(table.columns[k] for k in sortfree_keys)
            fused, payload_picks, rep, occupied, unplaced = \
                sharded_sortfree_segment_agg(
                    jnp.stack(cols, axis=1), kw, jnp.stack(masks, axis=1),
                    valid, num_segments, num_segments - 1,
                    mesh=shard_route[0], axis=shard_route[1],
                    backend=backend, moments=kernel_moments,
                    payloads=tuple(payload_specs))
            sortfree_extras = (rep, occupied, unplaced)
        elif shard_route is not None:
            from repro.launch.sharded_agg import sharded_fused_segment_agg
            res = sharded_fused_segment_agg(
                jnp.stack(cols, axis=1), seg.astype(jnp.int32),
                jnp.stack(masks, axis=1), num_segments, mesh=shard_route[0],
                axis=shard_route[1], backend=backend,
                moments=kernel_moments, assume_sorted=True,
                payloads=tuple(payload_specs), layout=layout,
                seg_map=seg_map)
            fused, payload_picks = res if payload_specs else (res, ())
        else:
            from repro.reliability import faults as _faults
            _faults.fail("kernel_launch")
            fused = fused_segment_agg(
                jnp.stack(cols, axis=1), seg.astype(jnp.int32),
                jnp.stack(masks, axis=1), num_segments, backend=backend,
                moments=kernel_moments, assume_sorted=True, layout=layout)
        for j, (u, c) in enumerate(zip(kernel_updates, upd_col)):
            f = u.fields[0]
            d = jnp.asarray(outer_vals[f]).dtype
            g, key = masks[c], cols[c]
            if u.kind == "arg_group":
                minimize = u.op in ("<", "<=")
                best = fused[c, 2 if minimize else 3].astype(d)
                if upd_mname[j] is not None:
                    pick = _index_row_to_pick(
                        fused[c, ARGMIN_ROW if minimize else ARGMAX_ROW],
                        n, tie_first=u.op in ("<", ">"))
                    pre = (payload_picks[payload_slot[j]]
                           if j in payload_slot else None)
                    _arg_select_from_index(u, outer_vals, col_env, best,
                                           pick, n, out, payloads=pre)
                else:
                    worst = _recognize._MINMAX_ID[
                        "min" if minimize else "max"](d)
                    masked = jnp.where(g, key.astype(d), worst)
                    _arg_group_select(u, outer_vals, col_env, g, masked,
                                      best, row_seg(), num_segments, out)
                continue
            r = fused[c, {"sum": 0, "min": 2, "max": 3}[u.kind]].astype(d)
            if u.kind == "sum":
                out[f] = outer_vals[f] + r
            elif u.kind == "min":
                out[f] = jnp.minimum(outer_vals[f], r)
            else:
                out[f] = jnp.maximum(outer_vals[f], r)
    if rest:
        out.update(_grouped_recognized(agg, rows, outer_vals, valid,
                                       row_seg(), num_segments,
                                       updates=tuple(rest)))
    if sortfree_keys is not None:
        # the caller pre-checked rest == [] and kernel_updates != [], so
        # sortfree_extras was always produced on this path
        return out, sortfree_extras
    return out


def _index_row_to_pick(idx_row: jax.Array, n: int,
                       tie_first: bool) -> jax.Array:
    """Convert a kernel index-moment row (f32, tie identity ±inf for empty
    segments) to the int32 pick convention of the select tails: ``n`` is
    the empty sentinel for first-attaining tie order, ``-1`` for
    last-attaining.  The ±inf → sentinel mapping happens in f32, BEFORE
    the int cast (casting inf to int is undefined)."""
    if tie_first:
        return jnp.where(idx_row < n, idx_row, n).astype(jnp.int32)
    return jnp.where(idx_row >= 0, idx_row, -1).astype(jnp.int32)


def _arg_select_from_index(u, outer_vals, col_env, best, pick, n, out,
                           payloads=None) -> None:
    """Arg-extremum tail on the kernel's index moment: the attaining row
    arrives directly from the fused pass (tie order already applied), so
    the legacy hit-detection equality scan, the full-row candidate reduce,
    and the row-set-sized ``take(best, seg)`` all disappear — the only
    remaining data movement is ONE num_segments-sized payload take per
    payload column.  ``payloads`` (the sharded path) are per-segment
    candidates already gathered shard-locally; then no local take runs at
    all.  The beat-compare against the pre-loop state is unchanged."""
    kf = u.fields[0]
    got = (pick >= 0) & (pick < n)
    cmp = {"<": best < outer_vals[kf], "<=": best <= outer_vals[kf],
           ">": best > outer_vals[kf], ">=": best >= outer_vals[kf]}[u.op]
    beat = cmp & got
    out[kf] = jnp.where(beat, best, outer_vals[kf])
    safe = jnp.clip(pick, 0, n - 1)
    for i, (f, pe) in enumerate(zip(u.fields[1:], u.exprs[1:])):
        pd = jnp.asarray(outer_vals[f]).dtype
        if payloads is not None:
            pv_pick = payloads[i].astype(pd)
        else:
            pv = jnp.broadcast_to(jnp.asarray(eval_expr(pe, col_env), pd),
                                  (n,))
            pv_pick = jnp.take(pv, safe)
        out[f] = jnp.where(beat, pv_pick, outer_vals[f])


def _arg_group_select(u, outer_vals, col_env, g, masked, best, seg, num_segments,
                      out) -> None:
    """Legacy tail of the grouped argmin/argmax lowering (the jnp
    recognized path and the >2^24-row kernel fallback): given the
    per-segment key extremum ``best``, pick the attaining row with a
    hit-detection equality scan (first for strict comparisons, last for
    non-strict — matching the sequential loop's tie order), gather the
    payload columns, and beat-compare against the pre-loop state.  The
    fused path replaces this with ``_arg_select_from_index`` (the kernel's
    index moment), which issues no row-capacity-sized gather."""
    n = masked.shape[0]
    idx = jnp.arange(n)
    hit = g & (masked == jnp.take(best, seg))
    cand = jnp.where(hit, idx, (n if u.op in ("<", ">") else -1))
    pickfn = jax.ops.segment_min if u.op in ("<", ">") else jax.ops.segment_max
    pick = pickfn(cand, seg, num_segments=num_segments)
    _arg_select_from_index(u, outer_vals, col_env, best, pick, n, out)


def _grouped_recognized(agg, rows, outer_vals, valid, seg, num_segments,
                        updates=None):
    """Segment-vectorized recognized aggregation on ``jax.ops.segment_*``
    (``updates`` restricts to a subset — used by the fused path for the
    kinds the kernel does not cover)."""
    col_env = dict(outer_vals)
    col_env.update(rows)
    out: dict[str, jax.Array] = {}
    n = valid.shape[0]
    idx = jnp.arange(n)
    for u in (agg.recognized if updates is None else updates):
        g = valid
        if u.guard is not None:
            g = g & jnp.asarray(eval_expr(u.guard, col_env), bool)
        if u.kind in ("sum", "prod", "min", "max"):
            f = u.fields[0]
            d = jnp.asarray(outer_vals[f]).dtype
            e = jnp.broadcast_to(jnp.asarray(eval_expr(u.exprs[0], col_env), d), (n,))
            if u.kind == "sum":
                out[f] = outer_vals[f] + jax.ops.segment_sum(
                    jnp.where(g, e, 0), seg, num_segments=num_segments)
            elif u.kind == "prod":
                out[f] = outer_vals[f] * jax.ops.segment_prod(
                    jnp.where(g, e, 1), seg, num_segments=num_segments)
            elif u.kind == "min":
                r = jax.ops.segment_min(
                    jnp.where(g, e, _recognize._MINMAX_ID["min"](d)), seg,
                    num_segments=num_segments)
                out[f] = jnp.minimum(outer_vals[f], r)
            else:
                r = jax.ops.segment_max(
                    jnp.where(g, e, _recognize._MINMAX_ID["max"](d)), seg,
                    num_segments=num_segments)
                out[f] = jnp.maximum(outer_vals[f], r)
        elif u.kind == "arg_group":
            kf = u.fields[0]
            kd = jnp.asarray(outer_vals[kf]).dtype
            key = jnp.broadcast_to(jnp.asarray(eval_expr(u.exprs[0], col_env), kd), (n,))
            minimize = u.op in ("<", "<=")
            worst = _recognize._MINMAX_ID["min" if minimize else "max"](kd)
            masked = jnp.where(g, key, worst)
            segfn = jax.ops.segment_min if minimize else jax.ops.segment_max
            best = segfn(masked, seg, num_segments=num_segments)
            _arg_group_select(u, outer_vals, col_env, g, masked, best,
                              seg, num_segments, out)
        elif u.kind == "last":
            f = u.fields[0]
            pd = jnp.asarray(outer_vals[f]).dtype
            e = jnp.broadcast_to(jnp.asarray(eval_expr(u.exprs[0], col_env), pd), (n,))
            cand = jnp.where(g, idx, -1)
            pick = jax.ops.segment_max(cand, seg, num_segments=num_segments)
            got = pick >= 0
            out[f] = jnp.where(got, jnp.take(e, jnp.clip(pick, 0, n - 1)),
                               outer_vals[f])
        else:  # pragma: no cover
            raise ValueError(u.kind)
    return out


def _grouped_scan(agg, rows, outer_vals, valid, starts, seg, num_segments):
    """Generic grouped custom aggregate: ONE segmented scan pass — state
    resets at segment starts; per-segment final states gathered at segment
    ends and terminated."""
    jagg = agg.as_jax_aggregate(outer_vals, deferred_init=False)
    init_state = jagg.init()

    def step(state, xs):
        row, ok, is_start = xs
        st = jax.tree.map(lambda i, s: jnp.where(is_start, i, s),
                          init_state, state)
        new = jagg.accumulate(st, row)
        new = jax.tree.map(lambda a, b: jnp.where(ok, a, b), new, st)
        return new, new

    n = valid.shape[0]
    state0 = jax.tree.map(lambda x: x, init_state)
    _, states = lax.scan(step, state0, (rows, valid, starts))

    # last row index of each segment
    idx = jnp.arange(n)
    cand = jnp.where(valid, idx, -1)
    last = jax.ops.segment_max(cand, seg, num_segments=num_segments)
    safe = jnp.clip(last, 0, n - 1)
    seg_states = jax.tree.map(lambda s: jnp.take(s, safe, axis=0), states)
    terms = jax.vmap(jagg.terminate)(seg_states)
    out = dict(zip(agg.terminate_vars, terms))
    # empty segments fall back to pre-loop values
    got = last >= 0
    for v in agg.terminate_vars:
        out[v] = jnp.where(got, out[v], outer_vals.get(v, jnp.zeros_like(out[v])))
    return out
