"""Mesh-sharded fused segmented aggregation: the distributed grouped hot path.

``core.aggregate.shard_merge`` already merges partial aggregate states over
ICI, and the fused segment-aggregate kernel's per-segment moments are
exactly such mergeable state: sum and count rows add across shards, min and
max rows extremize.  ``sharded_fused_segment_agg`` therefore runs the
kernel once per *row shard* under ``shard_map`` and all-reduces the
(C, 4, num_segments) moment tensor — ``lax.psum`` on the sum/count rows,
``lax.pmin``/``lax.pmax`` on the min/max rows.  That is the same algebra
``shard_merge`` left-folds, expressed as native collectives so XLA
schedules one fused all-reduce per moment row instead of an all-gather
plus a sequential fold (``moment_merge_aggregate`` exposes the fold form
so tests can pin the two against each other).

Arg-extremum state is mergeable too: when the kernel's INDEX MOMENT is
requested (rows 4/5 — the tie-ordered attaining row index), the shard
merge extends to the lexicographic (key, global_row) ``pmin``/``pmax``
(``_merge_index_rows``), and payload selection stays SHARD-LOCAL: each
shard takes its own (num_segments,)-sized payload candidates from its
local rows and the winner's candidates combine with a masked ``psum``
(``payloads=``).  Every collective in the path moves O(num_segments)
elements per shard — the payload gather never touches the global row
set.

Routing is transparent: ``table_row_split`` reads the split a table
declares (``Table.shard_rows(mesh, axis)`` sets ``Table.row_split``, a
static pytree field, so it survives tracing — a Mosaic kernel cannot be
partitioned by GSPMD and must run under ``shard_map`` inside ``jit`` as
well) or, for an undeclared concrete table, detects arrays that carry a
``NamedSharding`` split over more than one device along dim 0
(``row_sharded_mesh``).  The grouped executors (``core/executors.py``
grouped ``AggCall`` dispatch, ``relational/engine.py`` ``GroupAgg``) send
such tables through the sharded entry with no caller changes.  The split
is per table: a replicated table in the same plan keeps the one-device
launch.  ``REPRO_SEGAGG_SHARDED=off`` disables routing.

On the sorted route each shard sorts its own rows: ``shard_local_segments``
runs ``Table.sort_by`` per shard under ``shard_map`` and numbers the
shard's segments, and the partial groups merge across shards by key,
never by local segment number — by declared key ranges (a shift of each
shard's segment ids by the groups before it) or by an all-gather of each
shard's bound-sized key table slotted into one global table
(``shard_local_merge`` picks from static metadata).  No op of the global
row count crosses devices.  Every shard's rows are then sorted by
segment, so the band pruning of ``kernels/segment_agg.py`` applies per
shard, and each shard's pruned grid only walks the segment tiles its band
actually touches.  Without a dense bound or declared ranges the group
sort stays one GSPMD sort of every row.

``sharded_sortfree_segment_agg`` is the SORT-FREE counterpart: rows
arrive in arbitrary order and each shard hash-slots its own rows
(relational/keyslot.py) before running the kernel in
``layout='unsorted'``.  Shard-local slot numbers are hash-order and
therefore NOT aligned across shards, so the merge is key-aligned
instead: every shard publishes its (num_segments,)-sized slot→key table
with one all-gather, re-slots the gathered (replicated) key set into one
global table — a deterministic computation every shard repeats
identically, no further collective — scatters its local (C, R, S) moment
tensor onto the global slots, and only then runs the same
psum/pmin/pmax + lexicographic arg-merge as the sorted path.  Every
collective still moves O(num_segments) elements per shard; no sort, no
row-sized exchange.

``num_segments`` sizes the all-reduce payload: the grouped executors pass
the dense group bound (relational/group_bound.py) when one is declared, so
the per-moment collectives move (C, 4, ~group count) elements instead of
(C, 4, row capacity) — ~25× less on the default bench shape.  The bound
is independent of the shard count: rows (not segments) are padded to a
multiple of it, so a bound smaller than the mesh axis still works (tail
shards just contribute moment identities).

Whole-plan fusion (relational/fuse.py) interacts with this routing at
one seam: a fused chain's right-side column gathers produce fresh
arrays whose sharding is whatever XLA picked, which would make
the gathered columns miss the split.  ``fuse._recommit_rows`` puts each
gathered column back on the left table's row NamedSharding, and the
chain's table keeps the left table's ``row_split``, so sharded fused
chains still take the O(num_segments)-per-shard merge paths above with
no changes here.
"""
from __future__ import annotations

from typing import NamedTuple, Optional

import jax
import jax.numpy as jnp
from jax import lax
from jax.sharding import Mesh, NamedSharding
from jax.sharding import PartitionSpec as P

from repro.core.aggregate import Aggregate
from repro.configs import flags
from repro.kernels.segment_agg import (ARGMAX_ROW, ARGMIN_ROW, MOMENTS,
                                       NEG_INF, POS_INF, _index_tie,
                                       _normalize, _pad_rows, _row_fills,
                                       _validate_sorted, fused_segment_agg,
                                       has_index_moments, index_moment_ok,
                                       moment_rows, normalize_moments,
                                       resolve_backend)


def row_sharded_mesh(*arrays) -> Optional[tuple[Mesh, str]]:
    """(mesh, axis) when any concrete array carries a NamedSharding split
    over >1 device along dim 0; None for replicated arrays, tracers,
    composite row axes, or when ``REPRO_SEGAGG_SHARDED=off``."""
    if not flags.enabled("REPRO_SEGAGG_SHARDED"):
        return None
    for a in arrays:
        if a is None or isinstance(a, jax.core.Tracer):
            continue
        sh = getattr(a, "sharding", None)
        if not isinstance(sh, NamedSharding):
            continue
        spec = tuple(sh.spec)
        if not spec or spec[0] is None:
            continue
        ax = spec[0]
        if isinstance(ax, tuple):
            if len(ax) != 1:
                continue
            ax = ax[0]
        if sh.mesh.shape[ax] > 1:
            return sh.mesh, ax
    return None


def table_row_split(t) -> Optional[tuple[Mesh, str]]:
    """(mesh, axis) a Table's rows are split over: its declared
    ``row_split`` (static, so it holds under tracing), else the committed
    sharding of its concrete arrays; None when replicated, split over
    one device, or when ``REPRO_SEGAGG_SHARDED=off``."""
    if not flags.enabled("REPRO_SEGAGG_SHARDED"):
        return None
    if t.row_split is not None:
        mesh, axis = t.row_split[:2]
        return (mesh, axis) if mesh.shape[axis] > 1 else None
    return row_sharded_mesh(*t.columns.values(), t.valid)


def declare_row_split(t):
    """``t`` with its detected split declared (``Table.row_split``), so
    a ``jit`` over it still launches per shard; ``t`` itself when there
    is nothing to declare."""
    route = table_row_split(t)
    if route is None or t.row_split is not None:
        return t
    import dataclasses
    return dataclasses.replace(t, row_split=route)


def launch_rows(n: int, route: Optional[tuple[Mesh, str]]) -> int:
    """Rows one kernel launch walks: the shard's slice under ``route``
    (rows pad to a multiple of the shard count), all ``n`` without."""
    if route is None:
        return n
    mesh, axis = route
    return -(-n // mesh.shape[axis])


def shard_local_merge(t, keys, bound: Optional[int],
                      route: Optional[tuple[Mesh, str]]) -> Optional[str]:
    """How the sorted route merges the partial groups of a row-split
    table after each shard sorts its own rows (``shard_local_segments``),
    decided from static metadata only:

    * ``'ranges'``: the split is declared in ranges of keys that the
      group keys are a prefix of (``Table.shard_rows(ordered_by=...)``),
      so a group can only span the shards on either side of a boundary;
      each shard's segments shift by the groups before it, whatever the
      group count.
    * ``'keys'``: a dense bound is declared and the shards' key tables
      together hold no more entries than one shard has rows; the tables
      are all-gathered and slotted into one global table.
    * None: neither holds (or the rows do not divide evenly over the
      shards); the group sort stays one GSPMD sort of every row."""
    if route is None:
        return None
    mesh, axis = route
    nshards = mesh.shape[axis]
    if t.capacity % nshards:
        return None
    order = t.row_split[2] if t.row_split is not None \
        and len(t.row_split) > 2 else ()
    if tuple(keys) == tuple(order[:len(keys)]):
        return "ranges"
    if bound is not None and nshards * bound <= t.capacity // nshards:
        return "keys"
    return None


class ShardSegments(NamedTuple):
    """The shard-local group sort of a row-split table
    (``shard_local_segments``)."""
    #: the input table with each shard's rows sorted by the group keys
    table: object
    #: (N,) row-split segment ids: global ones (``'ranges'``), or each
    #: shard's own in [0, L], L its overflow slot (``'keys'``)
    seg: jax.Array
    #: ``'keys'``: (shards × L,) row-split map from each shard's segment
    #: ids to global ones; None for ``'ranges'``
    seg_map: Optional[jax.Array]
    #: group-key columns of the result, (num_segments,), replicated; the
    #: groups fill the first ``nseg`` slots
    keys: dict
    nseg: jax.Array
    #: groups beyond the dense bound (``'keys'``: entries left unslotted)
    overflow: jax.Array
    #: shard boundaries whose keys break the declared ranges (``'ranges'``)
    disorder: jax.Array

    def guard(self):
        """The traced ``ok`` guard for ``poison_overflow`` (None when a
        concrete result has nothing left to check); concrete failures
        raise, as ``check_group_overflow`` does."""
        from repro.relational.group_bound import GroupBoundOverflow
        if isinstance(self.overflow, jax.core.Tracer):
            return (self.overflow == 0) & (self.disorder == 0)
        if int(self.disorder):
            raise ValueError(
                f"row-split table: {int(self.disorder)} shard boundaries "
                f"break the key ranges the split declares (ordered_by)")
        if int(self.overflow):
            raise GroupBoundOverflow(
                f"grouped aggregation over a row-split table: "
                f"{int(self.overflow)} groups beyond the declared dense "
                f"bound — raise max_groups or drop the declaration")
        return None


def shard_local_segments(t, keys, num_segments: int, bound: Optional[int],
                         merge: str, mesh: Mesh, axis: str, *,
                         stable: bool = True) -> ShardSegments:
    """Group a row-split table shard by shard: under ``shard_map`` each
    shard sorts its own rows by ``keys`` (``Table.sort_by``, scope
    ``group_sort``, carrying the columns) and numbers its segments; the
    partial groups are then aligned across shards by key (scope
    ``shard_merge``) as ``merge`` says (``shard_local_merge``).  Nothing
    of the global row count crosses devices: ``'ranges'`` all-gathers
    each shard's group count and boundary keys and psums one
    (num_segments,) key table; ``'keys'`` all-gathers each shard's
    (bound,) key table.  Within a shard a group's rows keep their order,
    and shards follow each other, so row order inside every group is the
    table's.  ``stable=False``, for a caller whose aggregates ignore row
    order within a group, drops that guarantee and the stable sort's
    extra operand; the sort reads the keys back from its key operands
    either way (``Table.sort_by``)."""
    from repro.relational.engine import group_starts
    from repro.relational.table import Table
    keys = tuple(keys)
    nshards = mesh.shape[axis]
    n = t.capacity
    shard_n = n // nshards
    names = tuple(sorted(t.columns))
    overflow_slot = num_segments - 1
    width = min(overflow_slot, shard_n)   # 'keys': each shard's table

    def local(valid, *cols):
        st = Table(dict(zip(names, cols)), valid).sort_by(
            keys, stable=stable, carry_keys=False)
        m = st.mask()
        starts = group_starts(st, keys)
        lseg = jnp.cumsum(starts.astype(jnp.int32)) - 1
        nloc = jnp.sum(starts.astype(jnp.int32))
        with jax.named_scope("shard_merge"):
            if merge == "ranges":
                merged = _merge_ranges(st, m, starts, lseg, nloc, keys,
                                       num_segments, bound, axis)
            else:
                merged = _merge_keys(st, m, starts, lseg, nloc, keys,
                                     width, num_segments, axis, shard_n)
        return (tuple(st.columns[c] for c in names), m) + merged

    rows = P(axis)
    out_specs = (tuple(rows for _ in names), rows, rows,
                 {k: P() for k in keys}, P(), P(), P())
    if merge == "keys":
        out_specs += (rows,)
    # jit: an eager call runs as one program, not op by op per shard
    cols, valid, *merged = jax.jit(jax.shard_map(
        local, mesh=mesh, in_specs=(rows,) * (1 + len(names)),
        out_specs=out_specs, check_vma=False))(
            t.mask(), *(t.columns[c] for c in names))
    seg, kcols, nseg, over, dis, *smap = merged
    st = Table(dict(zip(names, cols)), valid, t.group_bound,
               row_split=t.row_split)
    return ShardSegments(st, seg, smap[0] if smap else None, kcols, nseg,
                         over, dis)


def global_segment_ids(seg, seg_map, num_segments: int, mesh: Mesh,
                       axis: str) -> jax.Array:
    """Per-row global segment ids from ``'keys'`` shard-local ones (a
    gather of each shard's rows from its own map), for the paths that
    need one id per row: jnp segment ops and the legacy arg tail."""
    def local(s, sm):
        return jnp.take(jnp.concatenate(
            [sm, jnp.full((1,), num_segments - 1, jnp.int32)]), s)
    return jax.jit(jax.shard_map(
        local, mesh=mesh, in_specs=(P(axis), P(axis)), out_specs=P(axis),
        check_vma=False))(seg, seg_map)


def _sort_lt(a, b):
    """``a < b`` in the order of ``Table.sort_by``'s sort (a NaN after
    every number, NaNs equal)."""
    if jnp.issubdtype(a.dtype, jnp.floating):
        return (a < b) | (~jnp.isnan(a) & jnp.isnan(b))
    return a.astype(jnp.int32) < b.astype(jnp.int32) \
        if a.dtype == jnp.bool_ else a < b


def _sort_eq(a, b):
    if jnp.issubdtype(a.dtype, jnp.floating):
        return (a == b) | (jnp.isnan(a) & jnp.isnan(b))
    return a == b


def _to_bits(x):
    if x.dtype == jnp.bool_:
        return x.astype(jnp.uint8)
    return lax.bitcast_convert_type(
        x, jnp.dtype(f"uint{8 * x.dtype.itemsize}"))


def _from_bits(u, dtype):
    if dtype == jnp.bool_:
        return u != 0
    return lax.bitcast_convert_type(u, dtype)


def _merge_ranges(st, m, starts, lseg, nloc, keys, num_segments, bound,
                  axis):
    """Declared key ranges: shard p's groups take the global slots after
    those of the shards before it, less one where its first group is the
    last group of the nearest non-empty shard before it."""
    from repro.relational.engine import keys_equal
    nval = jnp.sum(m.astype(jnp.int32))
    last = jnp.maximum(nval - 1, 0)
    first = [st.columns[k][0] for k in keys]
    final = [st.columns[k][last] for k in keys]
    g_nloc, g_nval, g_first, g_final = lax.all_gather(
        (nloc, nval, first, final), axis)
    p = jnp.arange(g_nloc.shape[0])
    # the nearest non-empty shard before each shard (-1: none)
    ne = jnp.where(g_nval > 0, p, -1)
    prev = jnp.concatenate([jnp.full((1,), -1, ne.dtype),
                            lax.cummax(ne)[:-1]])
    follows = (g_nval > 0) & (prev >= 0)
    q = jnp.maximum(prev, 0)
    a = [f[q] for f in g_final]     # the previous shard's last key
    joined = follows & jnp.all(jnp.stack(
        [keys_equal(x, y) for x, y in zip(a, g_first)]), axis=0)
    le = jnp.ones_like(follows)
    for x, y in reversed(list(zip(a, g_first))):
        le = _sort_lt(x, y) | (_sort_eq(x, y) & le)
    disorder = jnp.sum((follows & ~le).astype(jnp.int32))
    offset = (jnp.cumsum(g_nloc) - g_nloc
              - jnp.cumsum(joined.astype(g_nloc.dtype)))
    me = lax.axis_index(axis)
    overflow_slot = num_segments - 1
    nseg = jnp.sum(g_nloc) - jnp.sum(joined.astype(g_nloc.dtype))
    seg = jnp.where(m, jnp.minimum(lseg + offset[me], overflow_slot),
                    overflow_slot).astype(jnp.int32)
    # one writer per slot: a shard whose first group continues the
    # previous shard's leaves that key to it.  A max over sorted ids
    # (zero elsewhere) is a plain scatter to the TPU compiler, where a
    # set with dropped ids would lower to a sort.
    write = starts & ~(joined[me] & (lseg == 0))
    kcols = {}
    for k in keys:
        c = st.columns[k]
        bits = _to_bits(c)
        tab = jnp.zeros((num_segments,), bits.dtype).at[seg].max(
            jnp.where(write, bits, jnp.zeros((), bits.dtype)),
            indices_are_sorted=True, mode="promise_in_bounds")
        kcols[k] = _from_bits(lax.psum(tab, axis), c.dtype)
    over = jnp.int32(0) if bound is None else \
        jnp.maximum(nseg - bound, 0).astype(jnp.int32)
    return seg, kcols, nseg, over, disorder


def _merge_keys(st, m, starts, lseg, nloc, keys, width, num_segments, axis,
                shard_n):
    """Dense bound: each shard's (width,) key table is all-gathered and
    every shard slots the same gathered set into one global table
    (``keyslot.slot_ids_from_words``: replicated compute, no further
    collective); a shard's segments map to the slots of their keys."""
    from repro.relational.keyslot import key_words_for, slot_ids_from_words
    bucket = num_segments - 1
    # each segment's first row; a min over sorted ids stays a scatter
    pos = jax.ops.segment_min(
        jnp.where(m, jnp.arange(shard_n, dtype=jnp.int32), shard_n),
        jnp.clip(lseg, 0, width), num_segments=width + 1,
        indices_are_sorted=True)[:width]
    occ = jnp.arange(width) < nloc
    ktab = [jnp.take(st.columns[k], pos) for k in keys]
    gk = [lax.all_gather(c, axis).reshape(-1) for c in ktab]
    gocc = lax.all_gather(occ, axis).reshape(-1)
    slot, _owner, occupied, unplaced = slot_ids_from_words(
        key_words_for(gk), gocc, bucket)
    me = lax.axis_index(axis)
    smap = lax.dynamic_slice_in_dim(slot, me * width, width)
    # each global slot's key from its first gathered entry (shard order
    # is row order); a min-scatter, where slotting's own owner table
    # (unused, so compiled away) would lower to a sort
    total = gk[0].shape[0]
    first = jnp.full((num_segments,), total, jnp.int32).at[slot].min(
        jnp.arange(total, dtype=jnp.int32))
    rep = jnp.minimum(first, total - 1)
    kcols = {k: jnp.take(c, rep) for k, c in zip(keys, gk)}
    nseg = jnp.sum(occupied.astype(jnp.int32))
    lost = lax.psum(jnp.maximum(nloc - width, 0), axis)
    seg = jnp.where(m, jnp.minimum(lseg, width), width).astype(jnp.int32)
    return (seg, kcols, nseg, (lost + unplaced).astype(jnp.int32),
            jnp.int32(0), smap)


def _merge_moments(local: jax.Array, axis_name: str) -> jax.Array:
    """Cross-shard merge of a (C, 4, S) moment tensor: the shard_merge
    algebra (sum/count add, min/max extremize) as native collectives."""
    s = lax.psum(local[:, 0], axis_name)
    c = lax.psum(local[:, 1], axis_name)
    mn = lax.pmin(local[:, 2], axis_name)
    mx = lax.pmax(local[:, 3], axis_name)
    return jnp.stack([s, c, mn, mx], axis=1)


def _merge_index_rows(local: jax.Array, gmin: jax.Array, gmax: jax.Array,
                      offset, moments, axis_name: str) -> jax.Array:
    """Cross-shard ARG-merge of the index rows: each shard contributes its
    local (key, global_row) pair — ``local`` still holds shard-local row
    indices; ``offset`` (axis_index × shard rows) globalizes them, with
    the ±inf tie identities surviving the shift — and the merge is the
    lexicographic extremum: only shards attaining the already-merged key
    extremum enter their global row, reduced by ``pmin`` (first-attaining
    tie order: the smallest global row wins, and contiguous row sharding
    makes global row order the loop order) or ``pmax`` (last-attaining).
    The collective payload is one (S,) row per index moment —
    O(num_segments), never O(rows).  Returns the merged (C, 2, S) index
    rows (unrequested rows hold +inf)."""
    num_cols = local.shape[0]
    cols = []
    for c in range(num_cols):
        rows = []
        for which, row, gkey in (("argmin", ARGMIN_ROW, gmin[c]),
                                 ("argmax", ARGMAX_ROW, gmax[c])):
            tie_first = _index_tie(moments[c], which)
            if tie_first is None:
                rows.append(jnp.full_like(gkey, POS_INF))
                continue
            lkey = local[c, 2 if which == "argmin" else 3]
            cand = jnp.where(lkey == gkey, local[c, row] + offset,
                             POS_INF if tie_first else NEG_INF)
            rows.append(lax.pmin(cand, axis_name) if tie_first
                        else lax.pmax(cand, axis_name))
        cols.append(jnp.stack(rows))
    return jnp.stack(cols)


def moment_merge_aggregate(num_cols: int, num_segments: int) -> Aggregate:
    """The (C, 4, S) moment tensor as a ``core.aggregate.Aggregate`` whose
    state is the tensor itself: ``merge`` adds the sum/count rows and
    extremizes the min/max rows.  ``shard_merge(moment_merge_aggregate(...),
    local, axis)`` computes exactly what ``_merge_moments`` computes with
    collectives — tests pin the two against each other."""
    def identity():
        return jnp.stack(
            [jnp.zeros((num_cols, num_segments), jnp.float32),
             jnp.zeros((num_cols, num_segments), jnp.float32),
             jnp.full((num_cols, num_segments), POS_INF, jnp.float32),
             jnp.full((num_cols, num_segments), NEG_INF, jnp.float32)],
            axis=1)

    def merge(a, b):
        return jnp.stack([a[:, 0] + b[:, 0], a[:, 1] + b[:, 1],
                          jnp.minimum(a[:, 2], b[:, 2]),
                          jnp.maximum(a[:, 3], b[:, 3])], axis=1)

    return Aggregate("segagg_moments", init=identity, accumulate=merge,
                     terminate=lambda st: st, merge=merge,
                     identity=identity)


def sharded_fused_segment_agg(vals: jax.Array, segs: jax.Array,
                              valid: jax.Array, num_segments: int, *,
                              mesh: Mesh, axis: str = "data",
                              backend: str = "auto", block_rows: int = 256,
                              block_segs: int | None = None,
                              moments=MOMENTS, prune: bool = True,
                              assume_sorted: bool = False,
                              payloads=(), layout: str = "sorted",
                              seg_map: Optional[jax.Array] = None):
    """Row-sharded fused segmented aggregation over ``mesh.shape[axis]``
    devices: each shard runs ``fused_segment_agg`` on its contiguous row
    slice (full segment range), then the (C, R, num_segments) moment
    tensors merge with one all-reduce per moment row.  Same signature and
    result as ``fused_segment_agg`` (empty segments read
    [0, 0, +inf, -inf]); rows are padded to a multiple of the shard count
    with invalid rows repeating the last real segment id, so empty shards
    contribute identities and the per-shard pruned grids stay narrow.
    ``layout='unsorted'`` takes GLOBAL segment ids in any order (a cached
    slot table); each shard then runs the unsorted kernel layout and the
    merge is unchanged.

    Index moments (``argmin_*``/``argmax_*`` in ``moments``) extend the
    all-reduce algebra with the cross-shard ARG-merge: each shard's local
    attaining row is globalized (axis_index × shard rows) and merged as a
    lexicographic (key, global_row) ``pmin``/``pmax`` — see
    ``_merge_index_rows``.  ``payloads`` then keeps payload selection
    shard-local: each entry is ``(col, minimize, values)`` with ``values``
    a tuple of (N,) payload arrays; every shard gathers its OWN
    num_segments-sized candidate rows (local take, local rows only) and
    the winning shard's candidates are combined with one masked ``psum``
    per payload array.  The collective therefore moves O(num_segments)
    elements per shard, never O(rows).  With payloads the return value is
    ``(moments, picks)`` where ``picks[i]`` is a tuple of (S,) arrays in
    the payload dtypes (0 for segments with no attaining row — consumers
    gate on the index-row sentinel).

    Exactness: counts and min/max match the single-device kernel
    bit-for-bit; index rows and payload picks are bit-exact too (the
    lexicographic merge is order-independent); per-segment f32 sums are
    associativity-reordered across shard boundaries, so they are
    bitwise-equal when the addends are exactly representable
    (integer-valued data, the tests' parity case) and within normal f32
    rounding otherwise.

    ``seg_map`` (``shard_local_segments``' ``'keys'`` merge) makes
    ``segs`` each shard's own segment ids in [0, L], L its overflow
    slot, with ``seg_map`` the (shards × L,) row-split map from them to
    global ids: each shard's kernel walks L + 1 segments and its moments
    move onto the global segments before the merge."""
    from repro.reliability import faults as _faults
    _faults.fail("shard_launch")
    vals, valid = _normalize(jnp.asarray(vals), jnp.asarray(valid))
    segs = jnp.asarray(segs).astype(jnp.int32)
    nshards = mesh.shape[axis]
    if seg_map is not None and segs.shape[0] % nshards:
        raise ValueError("shard-local segment ids need rows that divide "
                         "evenly over the shards")
    num_cols = vals.shape[1]
    norm_moments = normalize_moments(moments, num_cols)
    indexed = has_index_moments(norm_moments)
    if payloads and not indexed:
        raise ValueError("shard-local payload gathering requires an index "
                         "moment on the key column (argmin_*/argmax_*)")

    # the sorted precondition only matters where band pruning runs — the
    # per-shard kernel backends; the jnp fallback is order-independent
    check_runtime = layout == "sorted" and _validate_sorted(
        segs, prune, assume_sorted, resolve_backend(backend))

    vals, segs, valid = _pad_rows(vals, segs, valid, nshards)
    n_p = vals.shape[0]
    if indexed and not index_moment_ok(n_p, block_rows):
        raise ValueError(
            f"index moments accumulate f32 row indices, exact only below "
            f"2^24 (padded) total rows; got {n_p}")
    shard_n = n_p // nshards
    sh = NamedSharding(mesh, P(axis))
    vals = jax.device_put(vals.astype(jnp.float32), sh)
    segs = jax.device_put(segs, sh)
    valid = jax.device_put(valid, sh)
    pv_flat: list[jax.Array] = []
    for _c, _minimize, pvs in payloads:
        for a in pvs:
            a = jnp.asarray(a)
            if a.shape[0] != n_p:       # mirror the row padding
                a = jnp.concatenate(
                    [a, jnp.zeros((n_p - a.shape[0],), a.dtype)])
            pv_flat.append(jax.device_put(a, sh))

    def local(v, s, g, *rest):
        pv = rest if seg_map is None else rest[1:]
        width = None if seg_map is None else rest[0].shape[0]
        out = fused_segment_agg(v, s, g,
                                num_segments if width is None else width + 1,
                                block_rows=block_rows,
                                block_segs=block_segs, backend=backend,
                                moments=norm_moments, prune=prune,
                                assume_sorted=True, layout=layout)
        if width is not None:
            sm_local = rest[0]
            fills = jnp.asarray(_row_fills(norm_moments),
                                jnp.float32).reshape(num_cols, -1, 1)
            with jax.named_scope("shard_merge"):
                # the local segment of each global one (width: none), so
                # the moments move by a gather: a set-scatter with the
                # unoccupied slots' shared target would lower to a sort
                inv = jnp.full((num_segments,), width, jnp.int32).at[
                    sm_local].min(jnp.arange(width, dtype=jnp.int32))
                out = jnp.where(inv < width,
                                jnp.take(out, jnp.minimum(inv, width - 1),
                                         axis=2), fills)
        with jax.named_scope("shard_merge"):
            return _merge_local(out, pv)

    def _merge_local(out, pv):
        if not indexed:
            return _merge_moments(out, axis), ()
        sm = lax.psum(out[:, 0], axis)
        cnt = lax.psum(out[:, 1], axis)
        mn = lax.pmin(out[:, 2], axis)
        mx = lax.pmax(out[:, 3], axis)
        offset = (lax.axis_index(axis) * shard_n).astype(out.dtype)
        gi = _merge_index_rows(out, mn, mx, offset, norm_moments, axis)
        merged = jnp.concatenate([jnp.stack([sm, cnt, mn, mx], axis=1), gi],
                                 axis=1)
        picks = []
        it = iter(pv)
        for c, minimize, pvs in payloads:
            gkey = mn[c] if minimize else mx[c]
            lkey = out[c, 2 if minimize else 3]
            lp = out[c, ARGMIN_ROW if minimize else ARGMAX_ROW]
            # exactly one shard owns the merged (key, global_row) winner:
            # global rows are unique, so the masked psum IS a select
            won = ((lkey == gkey) & (lp + offset == gi[c, 0 if minimize
                                                       else 1])
                   & (lp >= 0) & (lp < shard_n))
            safe = jnp.clip(lp, 0, shard_n - 1).astype(jnp.int32)
            per = []
            for _ in pvs:
                arr = next(it)
                gathered = jnp.take(arr, safe)       # (S,)-sized, local rows
                if gathered.dtype == jnp.bool_:
                    r = lax.psum(jnp.where(won, gathered.astype(jnp.int32),
                                           0), axis)
                    per.append(r != 0)
                else:
                    per.append(lax.psum(
                        jnp.where(won, gathered, jnp.zeros_like(gathered)),
                        axis))
            picks.append(tuple(per))
        return merged, tuple(picks)

    out_specs = (P(), tuple(tuple(P() for _ in pvs)
                            for _c, _m, pvs in payloads))
    extra = [] if seg_map is None else [seg_map]
    out, picks = jax.jit(jax.shard_map(
        local, mesh=mesh,
        in_specs=(P(axis),) * (3 + len(extra) + len(pv_flat)),
        out_specs=out_specs, check_vma=False))(vals, segs, valid, *extra,
                                               *pv_flat)
    if check_runtime:
        is_sorted = (jnp.all(segs[1:] >= segs[:-1])
                     if segs.shape[0] > 1 else jnp.bool_(True))
        out = jnp.where(is_sorted, out, jnp.float32(jnp.nan))
    if payloads:
        return out, picks
    return out


def sharded_fold_batch(vals: jax.Array, segs: jax.Array, valid: jax.Array,
                       pos: jax.Array, num_segments: int, *,
                       mesh: Mesh, axis: str = "data",
                       backend: str = "auto", block_rows: int = 256,
                       block_segs: int | None = None,
                       moments=MOMENTS, payloads=()):
    """Aggregate ONE micro-batch into a replicated (C, R, num_segments)
    moment tensor across a row-sharded mesh — the distributed half of the
    serving layer's incremental ingest.  The batch arrives already
    slotted against the resident table (``segs`` holds dense resident
    slot ids, so slot numbering is globally consistent by construction —
    no key exchange is needed, unlike ``sharded_sortfree_segment_agg``);
    each shard runs ``fused_segment_agg`` in ``layout='unsorted'`` over
    its row slice and the partial tensors merge with the standard
    psum/pmin/pmax algebra.  Index rows are globalized by ``pos`` — the
    batch rows' TABLE POSITIONS (f32-exact ints) — instead of the
    axis-index offset of ``_merge_index_rows``: the caller folds the
    result into a resident tensor whose index rows are position-numbered,
    and position order equals loop order over the appended table, so
    tie-order parity with a one-shot recompute holds by construction.
    ``payloads`` selects winner payload values shard-locally exactly as
    in ``sharded_fused_segment_agg`` (masked psum keyed on the merged
    (key, position) pair).  Every collective moves O(num_segments)
    elements per shard.  Returns ``(moments, picks)`` — replicated, ready
    for ``core.aggregate.fold_moments`` against the resident tensor."""
    from repro.reliability import faults as _faults
    _faults.fail("shard_launch")
    vals, valid = _normalize(jnp.asarray(vals), jnp.asarray(valid))
    segs = jnp.asarray(segs).astype(jnp.int32)
    pos = jnp.asarray(pos, jnp.float32)
    nshards = mesh.shape[axis]
    num_cols = vals.shape[1]
    norm_moments = normalize_moments(moments, num_cols)
    indexed = has_index_moments(norm_moments)
    if payloads and not indexed:
        raise ValueError("shard-local payload gathering requires an index "
                         "moment on the key column (argmin_*/argmax_*)")

    n = vals.shape[0]
    pad = (-n) % nshards
    if pad:
        vals = jnp.pad(vals, ((0, pad), (0, 0)))
        segs = jnp.concatenate(
            [segs, jnp.full((pad,), num_segments - 1, jnp.int32)])
        valid = jnp.pad(valid, ((0, pad), (0, 0)))
        pos = jnp.pad(pos, (0, pad))
    n_p = vals.shape[0]
    if indexed and not index_moment_ok(n_p, block_rows):
        raise ValueError(
            f"index moments accumulate f32 row indices, exact only below "
            f"2^24 (padded) total rows; got {n_p}")
    shard_n = n_p // nshards
    sh = NamedSharding(mesh, P(axis))
    vals = jax.device_put(vals.astype(jnp.float32), sh)
    segs = jax.device_put(segs, sh)
    valid = jax.device_put(valid, sh)
    pos = jax.device_put(pos, sh)
    pv_flat: list[jax.Array] = []
    for _c, _minimize, pvs in payloads:
        for a in pvs:
            a = jnp.asarray(a)
            if a.shape[0] != n_p:
                a = jnp.concatenate(
                    [a, jnp.zeros((n_p - a.shape[0],), a.dtype)])
            pv_flat.append(jax.device_put(a, sh))

    def local(v, s, g, p, *pv):
        out = fused_segment_agg(v, s, g, num_segments,
                                block_rows=block_rows,
                                block_segs=block_segs, backend=backend,
                                moments=norm_moments, layout="unsorted")
        sm = lax.psum(out[:, 0], axis)
        cnt = lax.psum(out[:, 1], axis)
        mn = lax.pmin(out[:, 2], axis)
        mx = lax.pmax(out[:, 3], axis)
        if not indexed:
            return jnp.stack([sm, cnt, mn, mx], axis=1), ()
        # globalize each attaining LOCAL row to its table position, then
        # merge lexicographically on (key, position)
        gi_cols = []
        for c in range(num_cols):
            rows = []
            for which, row, gkey in (("argmin", ARGMIN_ROW, mn[c]),
                                     ("argmax", ARGMAX_ROW, mx[c])):
                tie_first = _index_tie(norm_moments[c], which)
                if tie_first is None:
                    rows.append(jnp.full_like(gkey, POS_INF))
                    continue
                ident = POS_INF if tie_first else NEG_INF
                lkey = out[c, 2 if which == "argmin" else 3]
                lp = out[c, row]
                inr = (lp >= 0) & (lp < shard_n)
                safe = jnp.clip(lp, 0, shard_n - 1).astype(jnp.int32)
                cand = jnp.where((lkey == gkey) & inr, jnp.take(p, safe),
                                 ident)
                rows.append(lax.pmin(cand, axis) if tie_first
                            else lax.pmax(cand, axis))
            gi_cols.append(jnp.stack(rows))
        gi = jnp.stack(gi_cols)
        merged = jnp.concatenate(
            [jnp.stack([sm, cnt, mn, mx], axis=1), gi], axis=1)
        picks = []
        it = iter(pv)
        for c, minimize, pvs in payloads:
            gkey = mn[c] if minimize else mx[c]
            lkey = out[c, 2 if minimize else 3]
            lp = out[c, ARGMIN_ROW if minimize else ARGMAX_ROW]
            inr = (lp >= 0) & (lp < shard_n)
            safe = jnp.clip(lp, 0, shard_n - 1).astype(jnp.int32)
            # positions are unique across the table, so exactly one shard
            # matches the merged position — the masked psum IS a select
            won = ((lkey == gkey) & inr
                   & (jnp.take(p, safe) == gi[c, 0 if minimize else 1]))
            per = []
            for _ in pvs:
                arr = next(it)
                gathered = jnp.take(arr, safe)
                if gathered.dtype == jnp.bool_:
                    r = lax.psum(jnp.where(won, gathered.astype(jnp.int32),
                                           0), axis)
                    per.append(r != 0)
                else:
                    per.append(lax.psum(
                        jnp.where(won, gathered, jnp.zeros_like(gathered)),
                        axis))
            picks.append(tuple(per))
        return merged, tuple(picks)

    out_specs = (P(), tuple(tuple(P() for _ in pvs)
                            for _c, _m, pvs in payloads))
    return jax.shard_map(
        local, mesh=mesh,
        in_specs=(P(axis),) * (4 + len(pv_flat)),
        out_specs=out_specs, check_vma=False)(vals, segs, valid, pos,
                                              *pv_flat)


def sharded_sortfree_segment_agg(vals: jax.Array, key_words: jax.Array,
                                 valid: jax.Array, rowm: jax.Array,
                                 num_segments: int, bucket: int, *,
                                 mesh: Mesh, axis: str = "data",
                                 backend: str = "auto",
                                 block_rows: int = 256,
                                 block_segs: int | None = None,
                                 moments=MOMENTS, payloads=()):
    """Sort-free row-sharded fused segmented aggregation: hash-slotted
    segment ids per shard, key-aligned cross-shard merge.

    ``key_words`` is the (N, K) canonical uint32 key matrix
    (``keyslot.key_words_for``) and ``rowm`` the (N,) row-validity mask
    the slotting honors (per-column guards still arrive via ``valid``).
    Each shard assigns its rows slots in ``[0, bucket)`` independently
    (``slot_ids_from_words``), runs ``fused_segment_agg`` in
    ``layout='unsorted'`` on its slice, then aligns slots globally:
    the shard-local slot→key tables are all-gathered (one
    O(num_segments·K) collective), every shard re-slots the identical
    gathered key set into one global table (replicated compute, so no
    further exchange), and the local moment tensor is scattered onto the
    global slots — unoccupied and unplaced slots park on the overflow
    slot, whose merged content is never read as valid output.  From
    there the merge algebra is exactly ``sharded_fused_segment_agg``'s:
    psum/pmin/pmax per moment row, the lexicographic (key, global_row)
    arg-merge for index rows, shard-local O(num_segments) payload
    gathers combined by masked psum.

    Returns ``(moments, picks, rep_rows, occupied, unplaced)``:
    ``moments`` the merged (C, R, num_segments) tensor, ``picks`` the
    per-payload (S,)-sized winner values (empty tuple without
    ``payloads``), ``rep_rows`` (S,) int32 global representative row per
    global slot (input-row indexing; ``N``-sentinel where unoccupied),
    ``occupied`` (S,) bool, and ``unplaced`` the total count of valid
    rows (plus gathered keys) the bucket could not hold — the caller
    validates it with ``keyslot.check_slot_overflow``.
    """
    from repro.relational.keyslot import slot_ids_from_words
    from repro.reliability import faults as _faults

    _faults.fail("shard_launch")
    vals, valid = _normalize(jnp.asarray(vals), jnp.asarray(valid))
    kw = jnp.asarray(key_words)
    rowm = jnp.asarray(rowm, bool)
    nshards = mesh.shape[axis]
    num_cols = vals.shape[1]
    norm_moments = normalize_moments(moments, num_cols)
    indexed = has_index_moments(norm_moments)
    if payloads and not indexed:
        raise ValueError("shard-local payload gathering requires an index "
                         "moment on the key column (argmin_*/argmax_*)")

    n = vals.shape[0]
    pad = (-n) % nshards
    if pad:
        # pad rows are invalid everywhere: they never slot, never
        # contribute, and keep padded-space row indices == input indices
        vals = jnp.pad(vals, ((0, pad), (0, 0)))
        kw = jnp.pad(kw, ((0, pad), (0, 0)))
        valid = jnp.pad(valid, ((0, pad), (0, 0)))
        rowm = jnp.pad(rowm, (0, pad))
    n_p = vals.shape[0]
    if indexed and not index_moment_ok(n_p, block_rows):
        raise ValueError(
            f"index moments accumulate f32 row indices, exact only below "
            f"2^24 (padded) total rows; got {n_p}")
    shard_n = n_p // nshards
    sh = NamedSharding(mesh, P(axis))
    vals = jax.device_put(vals.astype(jnp.float32), sh)
    kw = jax.device_put(kw, sh)
    valid = jax.device_put(valid, sh)
    rowm = jax.device_put(rowm, sh)
    pv_flat: list[jax.Array] = []
    for _c, _minimize, pvs in payloads:
        for a in pvs:
            a = jnp.asarray(a)
            if a.shape[0] != n_p:
                a = jnp.concatenate(
                    [a, jnp.zeros((n_p - a.shape[0],), a.dtype)])
            pv_flat.append(jax.device_put(a, sh))

    nrows_m = moment_rows(norm_moments)
    fills = jnp.asarray(_row_fills(norm_moments),
                        jnp.float32).reshape(num_cols, nrows_m, 1)

    def local(v, k, g, rm, *pv):
        seg, owner, occ, unpl = slot_ids_from_words(k, rm, bucket)
        out = fused_segment_agg(v, seg, g, num_segments,
                                block_rows=block_rows,
                                block_segs=block_segs, backend=backend,
                                moments=norm_moments, layout="unsorted")
        # publish this shard's slot→key table; re-slot the gathered set
        # into ONE global table (identical on every shard — replicated
        # compute over all-gathered data, not another collective)
        ktab = jnp.take(k, jnp.clip(owner, 0, shard_n - 1), axis=0)
        gk = lax.all_gather(ktab, axis)                # (nshards, S-1, K)
        gocc = lax.all_gather(occ, axis)
        gown = lax.all_gather(owner, axis)
        eslot, eowner, gocc_glob, unpl_glob = slot_ids_from_words(
            gk.reshape(nshards * bucket, k.shape[1]), gocc.reshape(-1),
            bucket)
        me = lax.axis_index(axis)
        mine = lax.dynamic_slice_in_dim(eslot, me * bucket, bucket)
        # scatter local moments onto global slots; unoccupied local slots
        # (identity fills) and globally-unplaced keys park on overflow
        tgt = jnp.concatenate([jnp.where(occ, mine, bucket),
                               jnp.full((1,), bucket, jnp.int32)])
        glocal = jnp.broadcast_to(fills, out.shape).at[:, :, tgt].set(out)

        sm = lax.psum(glocal[:, 0], axis)
        cnt = lax.psum(glocal[:, 1], axis)
        mn = lax.pmin(glocal[:, 2], axis)
        mx = lax.pmax(glocal[:, 3], axis)
        if indexed:
            offset = (me * shard_n).astype(out.dtype)
            gi = _merge_index_rows(glocal, mn, mx, offset, norm_moments,
                                   axis)
            merged = jnp.concatenate(
                [jnp.stack([sm, cnt, mn, mx], axis=1), gi], axis=1)
        else:
            merged = jnp.stack([sm, cnt, mn, mx], axis=1)

        picks = []
        it = iter(pv)
        for c, minimize, pvs in payloads:
            gkey = mn[c] if minimize else mx[c]
            lkey = glocal[c, 2 if minimize else 3]
            lp = glocal[c, ARGMIN_ROW if minimize else ARGMAX_ROW]
            won = ((lkey == gkey)
                   & (lp + offset == gi[c, 0 if minimize else 1])
                   & (lp >= 0) & (lp < shard_n))
            safe = jnp.clip(lp, 0, shard_n - 1).astype(jnp.int32)
            per = []
            for _ in pvs:
                arr = next(it)
                gathered = jnp.take(arr, safe)       # (S,)-sized, local rows
                if gathered.dtype == jnp.bool_:
                    r = lax.psum(jnp.where(won, gathered.astype(jnp.int32),
                                           0), axis)
                    per.append(r != 0)
                else:
                    per.append(lax.psum(
                        jnp.where(won, gathered, jnp.zeros_like(gathered)),
                        axis))
            picks.append(tuple(per))

        # global representative rows: decode each global slot's winning
        # entry back to (shard, local slot) and globalize the local owner
        # (padded-space indices == input-row indices: padding is a tail)
        safe_e = jnp.clip(eowner, 0, nshards * bucket - 1)
        rep = jnp.where(gocc_glob,
                        (safe_e // bucket) * shard_n
                        + jnp.take(gown.reshape(-1), safe_e), n_p)
        rep_full = jnp.concatenate(
            [rep.astype(jnp.int32), jnp.full((1,), n_p, jnp.int32)])
        occ_full = jnp.concatenate([gocc_glob, jnp.zeros((1,), bool)])
        unpl_tot = lax.psum(unpl, axis) + unpl_glob
        return merged, tuple(picks), rep_full, occ_full, unpl_tot

    out_specs = (P(), tuple(tuple(P() for _ in pvs)
                            for _c, _m, pvs in payloads), P(), P(), P())
    return jax.shard_map(
        local, mesh=mesh,
        in_specs=(P(axis),) * (4 + len(pv_flat)),
        out_specs=out_specs, check_vma=False)(vals, kw, valid, rowm,
                                              *pv_flat)
