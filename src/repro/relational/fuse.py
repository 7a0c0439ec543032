"""Whole-plan fusion: Filter/Project/Join chains collapse into one
aggregate input — no intermediate Table between the join probe and the
kernel launch.

The per-node executor (engine._exec) materializes every operator: a
``Join → Filter → GroupAgg`` chain builds a full joined Table (one
row-sized gather per right column, all of them), then filters it, then
aggregates.  But the aggregate consumes only (a) a validity mask and
(b) the handful of columns it actually reads — which is exactly what
the fused chain produces directly:

* the join lowers to its *lookup* only (``engine._join_lookup``: keyslot
  hash build/probe — no row-sized sort, no gather), yielding a
  right-row index + found mask;
* Filter predicates never filter a Table — they evaluate against a lazy
  column resolver and AND into the validity mask, which reaches the
  kernel as the per-column guard mask (the PR-1 guard machinery);
* pure-Col Projects fold into a name → source-column mapping (zero
  data movement);
* only the columns the aggregate names (``needed``) materialize: left
  columns pass through by reference, right columns cost one clipped
  take each — strictly fewer gathers than the materialized join, which
  gathered every right column whether read or not.

The pass is a *pattern match*, not a planner: ``match_chain`` walks
Filter*/pure-Col-Project* down to an inner/left equi-Join and bails to
the materialized path on anything else (semi/anti joins are already
materialization-free filters; computed projections can mint columns the
chain cannot guard; OrderBy/Limit pin physical row semantics).  Parity
is gated seam-by-seam in tests/test_join_fuse.py: fused vs unfused
plans bit-for-bit on jnp AND interpret backends, plus a subprocess
8-way-mesh sharded case (the probe runs on per-shard-local rows; the
gathered right columns are re-committed to the left table's row
sharding so the O(num_segments) merge route still engages).

Kill switch: ``REPRO_PLAN_FUSE=off`` restores per-node materialization
(the bench "materialized" arm pins it).
"""
from __future__ import annotations

from dataclasses import dataclass
from typing import Any, Callable, Mapping, Optional

import jax
import jax.numpy as jnp

from repro.configs import flags
from repro.core.loop_ir import (BinOp, Call, Col, Expr, UnOp, Where,
                                eval_expr)
from .plan import Filter, Join, Plan, Project
from .table import Table

__all__ = ["fuse_enabled", "match_chain", "execute_chain",
           "fused_child_table", "fused_chain_result", "FusedChain",
           "ChainResult"]


def fuse_enabled() -> bool:
    """Kill switch for the whole-plan fusion pass (default: on).
    ``REPRO_PLAN_FUSE=off`` restores per-node Table materialization."""
    return flags.enabled("REPRO_PLAN_FUSE")


@dataclass(frozen=True)
class ChainResult:
    """A fused chain's execution product: the thin aggregate-input Table
    plus the raw probe outputs, so a grouping consumer keyed on the join
    key can feed ``ridx`` directly as segment ids (engine GroupAgg's
    provide_slots bridge) instead of re-slotting the key column."""
    table: Table
    chain: "FusedChain"
    ridx: jax.Array
    found: jax.Array
    right_capacity: int


@dataclass(frozen=True)
class FusedChain:
    """A matched ``Filter*/Project* → Join`` chain, normalized to the
    join-output namespace: ``preds`` are the chain's Filter predicates
    rewritten through every intervening Project; ``src_of`` maps each
    chain-output column name to its join-output source column (None =
    identity, no Project in the chain)."""
    join: Join
    preds: tuple[Expr, ...]
    src_of: Optional[Mapping[str, str]]

    def resolve(self, name: str) -> str:
        if self.src_of is None:
            return name
        src = self.src_of.get(name)
        if src is None:
            raise KeyError(name)
        return src


def _rename_cols(e: Expr, mapping: Mapping[str, str]) -> Expr:
    """Rewrite every ``Col(out)`` to ``Col(mapping[out])`` — the Project
    fold.  (loop_ir.substitute replaces Var only, so the Col walk lives
    here.)  Raises KeyError when the expression names a column the
    Project does not produce — the caller bails to materialization,
    preserving the unfused path's error."""
    if isinstance(e, Col):
        return Col(mapping[e.name])
    if isinstance(e, BinOp):
        return BinOp(e.op, _rename_cols(e.lhs, mapping),
                     _rename_cols(e.rhs, mapping))
    if isinstance(e, UnOp):
        return UnOp(e.op, _rename_cols(e.operand, mapping))
    if isinstance(e, Where):
        return Where(_rename_cols(e.cond, mapping),
                     _rename_cols(e.t, mapping),
                     _rename_cols(e.f, mapping))
    if isinstance(e, Call):
        return Call(e.name, e.fn,
                    tuple(_rename_cols(a, mapping) for a in e.args))
    return e                                  # Const / Var


def match_chain(plan: Plan) -> Optional[FusedChain]:
    """Pattern-match a fusable ``Filter*/Project* → Join(inner|left)``
    chain; None means execute per-node.  Projects must be pure column
    selections (every expr a Col) — computed projections mint values the
    lazy resolver cannot guard and fall back."""
    preds: list[Expr] = []
    src_of: Optional[dict[str, str]] = None
    node = plan
    while True:
        if isinstance(node, Filter):
            # a Filter renames nothing: its pred is already in the same
            # namespace as everything collected so far
            preds.append(node.pred)
            node = node.child
            continue
        if isinstance(node, Project):
            if not all(isinstance(e, Col) for _, e in node.exprs):
                return None
            proj = {out: e.name for out, e in node.exprs}
            try:
                preds = [_rename_cols(p, proj) for p in preds]
                if src_of is None:
                    src_of = dict(proj)
                else:
                    src_of = {top: proj[cur]
                              for top, cur in src_of.items()}
            except KeyError:
                return None
            node = node.child
            continue
        if isinstance(node, Join) and node.how in ("inner", "left"):
            return FusedChain(node, tuple(preds), src_of)
        return None


class _ChainEnv(Mapping):
    """Mapping view the chain's predicates evaluate under: column names
    resolve lazily through the join lookup (left by reference, right by
    one memoized gather), everything else falls back to the scalar
    environment — the same shadowing order as engine._col_env (columns
    win)."""

    def __init__(self, resolver: Callable[[str], Any],
                 names: frozenset, env: Mapping[str, Any]):
        self._resolver = resolver
        self._names = names
        self._env = env

    def __getitem__(self, name):
        if name in self._names:
            return self._resolver(name)
        return self._env[name]

    def __iter__(self):
        return iter(self._names | set(self._env))

    def __len__(self):
        return len(self._names | set(self._env))


def _recommit_rows(arrays: list, template: Table) -> list:
    """Gathered right-side columns lose the left table's row sharding
    (the gather output lands wherever XLA puts it) — put them back on the
    left rows' NamedSharding, matching the ``row_split`` the chain's
    table keeps."""
    from repro.launch.sharded_agg import table_row_split
    route = table_row_split(template)
    if route is None:
        return arrays
    mesh, axis = route
    from jax.sharding import NamedSharding, PartitionSpec
    s = NamedSharding(mesh, PartitionSpec(axis))
    return [jax.device_put(a, s) for a in arrays]


def execute_chain(chain: FusedChain, catalog, env: Mapping[str, Any],
                  needed: tuple, _exec) -> Optional[ChainResult]:
    """Run a matched chain: join *lookup* (no materialized join),
    predicates folded into the validity mask (the kernel guard), and
    only the ``needed`` columns realized.  Returns None — fall back to
    per-node execution — when a needed/predicate column is not served
    by the join output (the unfused path then raises its own error)."""
    from .engine import _bmask, _join_lookup

    join = chain.join
    lt = _exec(join.left, catalog, env)
    rt = _exec(join.right, catalog, env)
    ridx, found = _join_lookup(lt, rt, join.left_key, join.right_key)
    is_left = join.how == "left"
    gidx = jnp.clip(ridx, 0, rt.capacity - 1)

    gathered: dict[str, jax.Array] = {}

    def col(name: str) -> jax.Array:
        # join-output namespace: left wins collisions; the right key
        # column never survives the join (engine._apply_join contract)
        if name in lt.columns:
            return lt.columns[name]
        if name in gathered:
            return gathered[name]
        if name == join.right_key or name not in rt.columns:
            raise KeyError(name)
        v = jnp.take(rt.columns[name], gidx, axis=0, mode="clip")
        if is_left:
            v = jnp.where(_bmask(found, v), v, jnp.zeros_like(v))
        v, = _recommit_rows([v], lt)
        gathered[name] = v
        return v

    names = frozenset(lt.columns) | (frozenset(rt.columns)
                                     - {join.right_key})
    cenv = _ChainEnv(col, names, env)

    valid = lt.mask() if is_left else lt.mask() & found
    try:
        for p in chain.preds:
            valid = valid & jnp.asarray(eval_expr(p, cenv), bool)
        cols: dict[str, jax.Array] = {}
        from_left = True
        for name in dict.fromkeys(needed):
            src = chain.resolve(name)
            cols[name] = col(src)
            from_left = from_left and src in lt.columns
    except KeyError:
        return None

    # the fused chain's rows are a subset of the LEFT table's rows, so
    # when every realized column is a left column the left bound still
    # covers every group the result can produce (exactly the
    # Filter/semi-join preservation rule); any gathered right column
    # voids it, as in the materialized join
    bound = lt.group_bound if from_left else None
    return ChainResult(Table(cols, valid, bound, row_split=lt.row_split),
                       chain, ridx, found, rt.capacity)


def fused_chain_result(child: Plan, catalog, env: Mapping[str, Any],
                       needed: tuple, _exec) -> Optional[ChainResult]:
    """Match + execute, keeping the probe outputs so the caller can feed
    them as segment ids (engine._probe_slot_mapping); None when the
    chain does not fuse (caller materializes per-node)."""
    if not fuse_enabled():
        return None
    chain = match_chain(child)
    if chain is None:
        return None
    return execute_chain(chain, catalog, env, needed, _exec)


def fused_child_table(child: Plan, catalog, env: Mapping[str, Any],
                      needed: tuple, _exec) -> Optional[Table]:
    """The one-call entry the aggregate executors use: match + execute,
    None when the chain does not fuse (caller materializes per-node)."""
    res = fused_chain_result(child, catalog, env, needed, _exec)
    return None if res is None else res.table
