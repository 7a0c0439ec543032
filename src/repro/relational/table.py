"""Columnar Table: structure-of-arrays with a validity mask.

XLA requires static shapes, so variable-cardinality relational results are
represented as fixed-capacity columns plus a boolean ``valid`` mask (invalid
rows are compacted to the tail by ``compress``).  This is the TPU-native
stand-in for a row-store result set; a cursor's "temp table" is simply a
materialized (concrete, block_until_ready) Table.
"""
from __future__ import annotations

import itertools
from dataclasses import dataclass, field
from typing import Any, Dict, Iterable, Mapping, Optional

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax

#: monotonic source for Table.version — every constructed Table (including
#: every functional-update result) gets a fresh token, so "same version"
#: certifies "same rows" for host-side caches
_VERSIONS = itertools.count(1)


@jax.tree_util.register_pytree_node_class
@dataclass
class Table:
    columns: Dict[str, jax.Array]
    valid: Optional[jax.Array] = None  # bool (capacity,) ; None => all valid
    #: declared dense bound on the distinct-group count of this table's
    #: rows (``declare_group_bound``); static metadata the grouped
    #: executors use to size segment tensors — see
    #: relational/group_bound.py.  Row-preserving ops propagate it (they
    #: cannot create new key combinations); concat drops it.
    group_bound: Optional[int] = None
    #: host-side identity token: unique per constructed Table, never
    #: propagated by the functional update ops (each returns a NEW
    #: version) and excluded from the pytree — derived caches (the
    #: serving layer's slot tables) key on it so a mutation can never be
    #: served stale data.  Not part of traced state.
    version: int = field(default_factory=lambda: next(_VERSIONS),
                         compare=False)
    #: (mesh, axis) the rows are split over (``shard_rows``), or (mesh,
    #: axis, keys) when the split is also declared to be in ranges of
    #: ``keys``: static metadata that rides in the pytree treedef, so a
    #: traced table still states its split and the grouped executors
    #: launch the kernel per row shard under ``shard_map`` (GSPMD cannot
    #: partition a Mosaic kernel).  Ops that keep the table's row axis
    #: propagate it; ops that move rows (take, compress, sort_by) keep
    #: only (mesh, axis); concat and head drop it.
    row_split: Optional[tuple] = None

    # -- pytree ---------------------------------------------------------------
    def tree_flatten(self):
        names = tuple(sorted(self.columns))
        children = tuple(self.columns[n] for n in names) + (self.valid,)
        return children, (names, self.group_bound, self.row_split)

    @classmethod
    def tree_unflatten(cls, aux, children):
        names, group_bound, row_split = aux
        cols = dict(zip(names, children[:-1]))
        return cls(cols, children[-1], group_bound, row_split=row_split)

    # -- construction ---------------------------------------------------------
    @staticmethod
    def from_columns(**cols) -> "Table":
        cols = {k: jnp.asarray(v) for k, v in cols.items()}
        return Table(cols)

    # -- basic properties -----------------------------------------------------
    @property
    def capacity(self) -> int:
        return next(iter(self.columns.values())).shape[0]

    @property
    def column_names(self) -> tuple[str, ...]:
        return tuple(self.columns)

    def mask(self) -> jax.Array:
        if self.valid is None:
            return jnp.ones(self.capacity, dtype=bool)
        return self.valid

    def count(self) -> jax.Array:
        return jnp.sum(self.mask().astype(jnp.int32))

    # -- row ops ---------------------------------------------------------------
    def filter(self, mask: jax.Array) -> "Table":
        return Table(dict(self.columns), self.mask() & mask,
                     self.group_bound, row_split=self.row_split)

    def project(self, names: Iterable[str]) -> "Table":
        return Table({n: self.columns[n] for n in names}, self.valid,
                     self.group_bound, row_split=self.row_split)

    def with_column(self, name: str, values: jax.Array) -> "Table":
        cols = dict(self.columns)
        cols[name] = values
        # a new column may have more distinct values than the declared
        # group bound covers, so the declaration does not survive
        return Table(cols, self.valid, row_split=self.row_split)

    def rename(self, mapping: Mapping[str, str]) -> "Table":
        cols = {mapping.get(k, k): v for k, v in self.columns.items()}
        return Table(cols, self.valid, self.group_bound,
                     row_split=self.row_split)

    def take(self, idx: jax.Array, idx_valid: Optional[jax.Array] = None) -> "Table":
        cols = {k: jnp.take(v, idx, axis=0, mode="clip")
                for k, v in self.columns.items()}
        base = jnp.take(self.mask(), idx, mode="clip")
        v = base if idx_valid is None else base & idx_valid
        return Table(cols, v, self.group_bound, row_split=self._split())

    def compress(self) -> "Table":
        """Stable-compact valid rows to the front (fixed capacity)."""
        m = self.mask()
        order = jnp.argsort(~m, stable=True)
        t = self.take(order)
        n = jnp.sum(m.astype(jnp.int32))
        return Table(t.columns, jnp.arange(self.capacity) < n,
                     self.group_bound, row_split=self._split())

    def sort_by(self, keys: Iterable[str], descending: Iterable[bool] = (),
                *, stable: bool = True, carry_keys: bool = True) -> "Table":
        """Multi-key sort, stable unless ``stable=False``; invalid rows
        sort last.

        ONE variadic ``lax.sort`` (scope ``group_sort``): the validity
        flag (invalid-last) leads, the transformed key columns follow in
        precedence order, and every column of shape ``(capacity,)``
        rides along as a payload operand in its own dtype.  The sorted
        table is read off the sort's outputs: each such column from its
        payload (key columns too, so their values come back bit for bit,
        not rebuilt from the transformed keys) and the mask from the
        sorted flag.  On a TPU v5e a carried 60M-row column adds 0.073 s
        to the sort, where gathering it by the sort's permutation takes
        0.75 s.  XLA drops the payloads no consumer reads, so the sort
        compiles and runs with the columns the plan uses, not every
        column traced here.  Only a column of another shape is gathered,
        by an iota payload's permutation (scope ``sort_gather``).

        Two operands fewer for a caller that needs neither: the TPU
        compiler implements a stable sort with one more iota operand
        (``stable=False`` drops it: rows of equal keys then come in no
        set order), and ``carry_keys=False`` reads each ascending key
        column back from its own sort operand, exact on the valid rows
        (an invalid row then holds the sort's fill)."""
        keys = list(keys)
        desc = list(descending) or [False] * len(keys)
        m = self.mask()
        ops = [(~m).astype(jnp.int8)]
        for k, d in zip(keys, desc):
            ops.append(_sort_key(self.columns[k], d, m))
        n = self.capacity
        from_key = {} if carry_keys else {
            k: i + 1 for i, (k, d) in enumerate(zip(keys, desc)) if not d}
        carried = [k for k, v in self.columns.items()
                   if v.shape == (n,) and k not in from_key]
        gathered = [k for k in self.columns
                    if k not in carried and k not in from_key]
        payload = [self.columns[k] for k in carried]
        if gathered:
            payload.append(lax.iota(jnp.int32, n))
        with jax.named_scope("group_sort"):
            res = lax.sort(tuple(ops) + tuple(payload), dimension=0,
                           is_stable=stable, num_keys=len(ops))
        out = dict(zip(carried, res[len(ops):]))
        out.update((k, res[i].astype(self.columns[k].dtype))
                   for k, i in from_key.items())
        if gathered:
            with jax.named_scope("sort_gather"):
                out.update((k, jnp.take(self.columns[k], res[-1], axis=0,
                                        mode="clip")) for k in gathered)
        return Table({k: out[k] for k in self.columns}, res[0] == 0,
                     self.group_bound, row_split=self._split())

    def head(self, n: int) -> "Table":
        c = self.compress()
        cols = {k: v[:n] for k, v in c.columns.items()}
        return Table(cols, c.mask()[:n], self.group_bound)

    def declare_group_bound(self, max_groups: int) -> "Table":
        """Declare a dense bound on how many distinct groups this table's
        rows can form (any key set the caller intends to group by).  The
        grouped executors (``GroupAgg`` and grouped ``AggCall``) size
        their segment tensors, the band-pruned kernel grid, and the
        sharded all-reduce payload by the bound's power-of-two bucket
        instead of the row capacity — and *validate* it: a concrete input
        with more groups raises eagerly, a traced one NaN-poisons the
        outputs.  See relational/group_bound.py.

        The *bucket* (not the raw value) is stored: it rides in the
        pytree treedef, so tables declared with nearby bounds share one
        treedef and jitted callers don't retrace per distinct value."""
        from .group_bound import bucket_group_bound
        return Table(dict(self.columns), self.valid,
                     bucket_group_bound(max_groups), row_split=self.row_split)

    def shard_rows(self, mesh, axis: str = "data",
                   ordered_by: Iterable[str] = ()) -> "Table":
        """Commit every column (and the validity mask) to a row sharding —
        ``PartitionSpec(axis)`` on dim 0 — over ``mesh``, and declare the
        split (``row_split``).  The grouped paths (``GroupAgg`` and
        grouped ``AggCall``) read the declaration, eagerly and under
        ``jit`` alike — no other caller changes needed: each shard sorts
        its own rows by the group keys and runs the segment-aggregate
        kernel on them, and the partial groups merge across devices by
        key (``launch/sharded_agg.py``).

        ``ordered_by`` declares that the split is also one of key
        ranges: every key tuple of a shard sorts at or before every key
        tuple of the next, as a table loaded in key order and cut into
        contiguous row ranges is (an MPP warehouse's range-distributed
        fact table).  A grouping by a prefix of those keys then merges
        only at the shard boundaries, whatever its group count.  The
        declaration is checked at run time: a violated one raises
        eagerly and poisons a traced result."""
        from jax.sharding import NamedSharding, PartitionSpec
        sh = NamedSharding(mesh, PartitionSpec(axis))
        cols = {k: jax.device_put(v, sh) for k, v in self.columns.items()}
        order = tuple(ordered_by)
        return Table(cols, jax.device_put(self.mask(), sh),
                     self.group_bound,
                     row_split=(mesh, axis) + ((order,) if order else ()))

    def _split(self) -> Optional[tuple]:
        """``row_split`` without the range declaration, for ops that move
        rows."""
        return None if self.row_split is None else self.row_split[:2]

    def materialize(self) -> "Table":
        """Force device materialization — models the cursor temp table."""
        cols = {k: jax.block_until_ready(jnp.asarray(v)) for k, v in self.columns.items()}
        v = None if self.valid is None else jax.block_until_ready(self.valid)
        return Table(cols, v, self.group_bound, row_split=self.row_split)

    def nbytes(self) -> int:
        tot = 0
        for v in self.columns.values():
            tot += int(np.prod(v.shape)) * v.dtype.itemsize
        return tot

    def to_numpy(self) -> dict[str, np.ndarray]:
        with jax.profiler.TraceAnnotation("table.to_host"):
            m = np.asarray(self.mask())
            return {k: np.asarray(v)[m] for k, v in self.columns.items()}


def _sort_key(col: jax.Array, descending: bool, valid: jax.Array) -> jax.Array:
    if col.dtype == jnp.bool_:
        col = col.astype(jnp.int32)
    key = -col if descending else col
    if jnp.issubdtype(key.dtype, jnp.floating):
        big = jnp.array(jnp.inf, dtype=key.dtype)
    else:
        big = jnp.array(jnp.iinfo(key.dtype).max, dtype=key.dtype)
    return jnp.where(valid, key, big)


def concat(a: Table, b: Table) -> Table:
    cols = {k: jnp.concatenate([a.columns[k], b.columns[k]], axis=0)
            for k in a.columns}
    return Table(cols, jnp.concatenate([a.mask(), b.mask()]))
