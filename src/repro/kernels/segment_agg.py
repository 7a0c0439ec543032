"""Pallas TPU kernel: fused grouped aggregation (the 𝒢_{AggΔ} hot path).

One pass over rows sorted by segment id computes SUM / COUNT / MIN / MAX
per segment simultaneously — the fused multi-aggregate the recognized
execution path of Aggify emits for grouped custom aggregates.  The kernel
accepts *multiple value columns per pass* (each with its own validity
mask, so differently-guarded recognized updates batch into one HBM
traversal) and tiles the *segment range* so the one-hot membership mask
always fits VMEM regardless of group cardinality.

TPU adaptation (vs a CUDA scatter-atomic formulation): atomics are not the
TPU model.  Instead each row-block materializes a one-hot membership mask
(rows × segment-tile) in VMEM and reduces with broadcast/select ops on the
VPU (8×128 lanes); partials accumulate into the output block, which stays
resident in VMEM across its whole visit run (output revisiting).  Rows are
pre-sorted by segment, so the mask is band-structured and the working set
is bounded by (BLOCK_ROWS × BLOCK_SEGS) — chosen by ``default_block_segs``
to respect a VMEM budget at a 128-lane-aligned tile width.

Band pruning (the default for the kernel backends): because rows are
sorted, row block *i* only intersects the contiguous band of segment tiles
``[min(segs_i) // BS, max(segs_i) // BS]``.  The grid is therefore NOT the
``(seg_tiles × row_blocks)`` cross product: a compact 1-D grid of
``row_blocks + seg_tiles - 1`` steps walks exactly the intersecting
``(row_block, seg_tile)`` pairs, carried into the kernel via
``pltpu.PrefetchScalarGridSpec`` step→block index maps (scalar prefetch,
so the index maps themselves read them).  Both the row-block index and the
segment-tile index are non-decreasing along the step sequence, so each
input block is fetched once and each output tile is written once — grid
cost O(row_blocks + seg_tiles) instead of O(row_blocks × seg_tiles).
``pruned_grid_steps`` reports the executed-step count so tests and
benchmarks can assert it.  Pruning requires the documented sorted-``segs``
precondition; see ``fused_segment_agg``.

``num_segments`` is the caller's static segment range: the grouped
executors pass a dense group bound (relational/group_bound.py) when one is
declared, which shrinks both the ``seg_tiles`` grid term
(``launched_grid_steps``) and the (C, 4, num_segments) output tensor
(``moment_tensor_bytes``) from row-capacity-sized to group-count-sized.

The step→block maps are scalar-prefetched into SMEM (two int32 per step,
1 MiB on v5e), so one launch holds at most ``MAX_PREFETCH_STEPS`` steps.
A longer sorted input is split into row ranges, one ``pallas_call``
each; every range accumulates into the previous range's output (aliased
in place), which is the same sum/count-add, min/max-extremize merge the
sharded path applies across devices.

Grid (unpruned fallback, ``prune=False``): (num_seg_tiles, num_row_blocks)
with row blocks iterating fastest.  Operands are lane-dense — rows run
along the 128-wide lane axis — because a (N, 1) column would be padded
to 128 lanes in HBM (128× its size) before the kernel could read it.
Block shapes in both layouts:
  vals  (C, BLOCK_ROWS)  f32          segs  (1, BLOCK_ROWS) i32
  valid (C, BLOCK_ROWS)  f32 (0/1)
  out   (4*C, BLOCK_SEGS)  row layout [4*c + m] with m = sum,count,min,max
Inside the kernel one small transpose turns each row block into
per-row columns for the (BLOCK_ROWS × BLOCK_SEGS) membership mask.

Execution backends (``fused_segment_agg``):
  * ``pallas``    — compiled kernel (real TPU).
  * ``interpret`` — the same kernel under the Pallas interpreter (CI/CPU
                    correctness; exercises the exact lowering).
  * ``jnp``       — pure ``jax.ops.segment_*`` fallback, identical math,
                    used on CPU/GPU where the interpreter loop would be
                    the bottleneck.
  * ``auto``      — pallas on TPU, jnp elsewhere.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

NEG_INF = float("-inf")
POS_INF = float("inf")

#: index of each fused value moment in the kernel output
MOMENTS = ("sum", "count", "min", "max")

#: optional *index* moments: the row index attaining the per-segment min
#: (row ``ARGMIN_ROW``) or max (row ``ARGMAX_ROW``), with the requesting
#: loop's tie order — ``*_first`` keeps the earliest attaining row (the
#: strict ``<``/``>`` comparison of a cursor loop never replaces an equal
#: key), ``*_last`` the latest (``<=``/``>=`` replaces on equality).  The
#: index accumulates as an f32 lexicographic (key, row) compare inside the
#: same band-pruned membership reduce, so it costs no extra grid steps;
#: exactness requires the (padded) row count below 2^24 (f32 integers).
INDEX_MOMENTS = ("argmin_first", "argmin_last", "argmax_first", "argmax_last")

#: moment-row offsets of the index rows (present only when a column
#: requests an index moment; the output then has 6 rows per column)
ARGMIN_ROW = 4
ARGMAX_ROW = 5

#: f32-exact row-index ceiling: above this the index moment is refused
INDEX_EXACT_ROWS = 1 << 24


def index_moment_ok(n: int, block_rows: int = 256) -> bool:
    """True when every row index the kernel can record — i.e. up to ``n``
    padded to a ``block_rows`` multiple — is exactly representable in the
    f32 accumulator.  The ONE gate shared by the kernel's own validation
    and the executors' use-index decision, so a row count just under the
    ceiling falls back to the legacy pick instead of tripping the
    kernel's raise."""
    return n + (-n) % block_rows < INDEX_EXACT_ROWS

#: TPU vector lane width — segment tiles are sized in multiples of it so
#: the membership-mask reduce never issues ragged lanes
LANE = 128

#: steps one band-pruned launch may hold: its two int32 step→block maps
#: are scalar-prefetched into SMEM (1 MiB on v5e; 2^16 steps use half)
MAX_PREFETCH_STEPS = 1 << 16


def default_block_segs(num_segments: int, block_rows: int = 256,
                       vmem_budget_elems: int = 1 << 19) -> int:
    """Largest 128-lane-aligned segment-tile width whose (block_rows × tile)
    membership mask stays under ``vmem_budget_elems`` f32 elements (default
    2 MB).  Invariants (asserted by tests): the result is a multiple of
    ``LANE``; it never exceeds the segment range rounded up to a lane
    multiple; and ``result * block_rows <= vmem_budget_elems`` whenever the
    budget admits at least one lane group (the floor is one 128-lane tile —
    narrower tiles would leave VPU lanes dead every cycle)."""
    budget = (vmem_budget_elems // max(block_rows, 1)) // LANE * LANE
    bs = max(LANE, budget)
    need = -(-num_segments // LANE) * LANE
    return int(min(need, bs))


# ---------------------------------------------------------------------------
# Moment normalization (shared by every backend and the sharded launcher)
# ---------------------------------------------------------------------------


def normalize_moments(moments, num_cols: int) -> tuple[tuple[str, ...], ...]:
    """Canonicalize ``moments`` to one validated tuple per column.

    Accepts either a flat tuple of moment names (applied to every column)
    or a per-column tuple of tuples.  Index moments imply their value
    extremum (``argmin_*`` adds ``min``, ``argmax_*`` adds ``max`` — the
    kernel's index merge reads the running extremum row).  A column may
    carry at most ONE tie order per extremum direction: ``argmin_first``
    and ``argmin_last`` share output row ``ARGMIN_ROW``, so requesting
    both on one column is a contract violation (callers split the column).
    Unknown moment names raise instead of being silently dropped."""
    known = MOMENTS + INDEX_MOMENTS
    if not moments or isinstance(moments[0], str):
        per_col = (tuple(moments),) * num_cols
    else:
        per_col = tuple(tuple(ms) for ms in moments)
    if len(per_col) != num_cols:
        raise ValueError(f"per-column moments: got {len(per_col)} entries "
                         f"for {num_cols} columns")
    out = []
    for ms in per_col:
        bad = [m for m in ms if m not in known]
        if bad:
            raise ValueError(f"unknown moment(s) {bad!r}; expected a subset "
                             f"of {known}")
        ms = set(ms)
        if "argmin_first" in ms and "argmin_last" in ms:
            raise ValueError("a column cannot carry both argmin_first and "
                             "argmin_last (one index row per extremum "
                             "direction) — use separate columns")
        if "argmax_first" in ms and "argmax_last" in ms:
            raise ValueError("a column cannot carry both argmax_first and "
                             "argmax_last (one index row per extremum "
                             "direction) — use separate columns")
        if "argmin_first" in ms or "argmin_last" in ms:
            ms.add("min")
        if "argmax_first" in ms or "argmax_last" in ms:
            ms.add("max")
        out.append(tuple(m for m in known if m in ms))
    return tuple(out)


def has_index_moments(moments: tuple[tuple[str, ...], ...]) -> bool:
    return any(m in INDEX_MOMENTS for ms in moments for m in ms)


def moment_rows(moments: tuple[tuple[str, ...], ...]) -> int:
    """Rows per column in the output tensor: 4 value rows, plus the two
    index rows when any column requests an index moment."""
    return 6 if has_index_moments(moments) else 4


def _index_tie(ms: tuple[str, ...], which: str):
    """Tie order of ``which`` ('argmin'/'argmax') for one column:
    True = first-attaining, False = last-attaining, None = not requested."""
    if which + "_first" in ms:
        return True
    if which + "_last" in ms:
        return False
    return None


def _row_fills(moments: tuple[tuple[str, ...], ...]) -> tuple[float, ...]:
    """Per-output-row init/identity values, column-major: [0, 0, +inf,
    -inf] for the value rows; the index rows hold the tie identity (+inf
    when the smallest attaining row wins, -inf when the largest does)."""
    nrows = moment_rows(moments)
    fills: list[float] = []
    for ms in moments:
        fills += [0.0, 0.0, POS_INF, NEG_INF]
        if nrows == 6:
            fills += [NEG_INF if _index_tie(ms, "argmin") is False
                      else POS_INF,
                      NEG_INF if _index_tie(ms, "argmax") is False
                      else POS_INF]
    return tuple(fills)


# ---------------------------------------------------------------------------
# Kernel bodies (shared between the pruned and unpruned grids)
# ---------------------------------------------------------------------------


def _init_out(out_ref, num_cols: int, block_segs: int,
              moments: tuple[tuple[str, ...], ...]) -> None:
    fills = _row_fills(moments)
    for r, f in enumerate(fills):
        out_ref[r, :] = jnp.full((block_segs,), f, out_ref.dtype)


def _extremum_with_index(out_ref, base: int, row: int, member, vbc, idxv,
                         block_val, tie_first: bool, minimize: bool) -> None:
    """Merge one row block's (key, row-index) pair into the resident
    extremum + index rows: the lexicographic compare of the index moment.
    ``block_val`` is the block's per-segment extremum; the attaining row
    within the block is the tie-ordered reduce over the rows matching it,
    and the merge with the resident tile compares keys first, indices on
    equality.  Must run before the extremum row is overwritten."""
    krow = base + (2 if minimize else 3)
    cur_k = out_ref[krow, :]
    cur_i = out_ref[base + row, :]
    hit = member & (vbc == block_val[None, :])
    if tie_first:
        bi = jnp.min(jnp.where(hit, idxv, POS_INF), axis=0)
        tie = jnp.minimum
    else:
        bi = jnp.max(jnp.where(hit, idxv, NEG_INF), axis=0)
        tie = jnp.maximum
    beats = block_val < cur_k if minimize else block_val > cur_k
    out_ref[base + row, :] = jnp.where(
        beats, bi, jnp.where(block_val == cur_k, tie(bi, cur_i), cur_i))


def _accum_rows(vals_ref, segs_ref, valid_ref, out_ref, seg_tile, row_base, *,
                block_segs: int, num_cols: int,
                moments: tuple[tuple[str, ...], ...]) -> None:
    """Accumulate one row block into the resident output tile ``seg_tile``
    (a traced i32 scalar on the pruned grid, a grid index otherwise).
    ``row_base`` is the global index of the block's first row — the index
    moments record ``row_base + local_row`` for the attaining row.

    The lane-dense (1|C, R) blocks are stacked into one f32 slab and
    transposed once, giving each quantity as an (R, 1) column.  Segment
    ids enter the slab tile-relative and clipped to [-1, BS], so f32
    holds them exactly whatever the global id range."""
    r = segs_ref.shape[1]
    nrows = moment_rows(moments)
    local = jnp.clip(segs_ref[...] - seg_tile * block_segs, -1,
                     block_segs).astype(jnp.float32)          # (1, R)
    slab = [local, vals_ref[...], valid_ref[...]]             # 1 + 2C rows
    k = 1 + 2 * num_cols
    if k % 8:
        slab.append(jnp.zeros((8 - k % 8, r), jnp.float32))
    cols = jnp.concatenate(slab, axis=0).T                    # (R, 8m)

    seg_iota = lax.broadcasted_iota(jnp.int32, (r, block_segs), 1)
    in_tile = cols[:, 0:1].astype(jnp.int32) == seg_iota      # (R, BS) band
    idxv = None
    if nrows == 6:
        idxv = (row_base + lax.broadcasted_iota(
            jnp.int32, (r, block_segs), 0)).astype(out_ref.dtype)

    for c in range(num_cols):
        ms = moments[c]
        base = nrows * c
        member = in_tile & (cols[:, 1 + num_cols + c:2 + num_cols + c] != 0)
        vbc = jnp.broadcast_to(cols[:, 1 + c:2 + c], (r, block_segs))
        if "sum" in ms:
            out_ref[base + 0, :] += jnp.sum(jnp.where(member, vbc, 0),
                                            axis=0)
        if "count" in ms:
            out_ref[base + 1, :] += jnp.sum(member.astype(out_ref.dtype),
                                            axis=0)
        amn = _index_tie(ms, "argmin")
        amx = _index_tie(ms, "argmax")
        if "min" in ms:
            bk = jnp.min(jnp.where(member, vbc, POS_INF), axis=0)
            if amn is not None:     # index merge reads the OLD extremum row
                _extremum_with_index(out_ref, base, ARGMIN_ROW, member, vbc,
                                     idxv, bk, tie_first=amn, minimize=True)
            out_ref[base + 2, :] = jnp.minimum(out_ref[base + 2, :], bk)
        if "max" in ms:
            bk = jnp.max(jnp.where(member, vbc, NEG_INF), axis=0)
            if amx is not None:
                _extremum_with_index(out_ref, base, ARGMAX_ROW, member, vbc,
                                     idxv, bk, tie_first=amx, minimize=False)
            out_ref[base + 3, :] = jnp.maximum(out_ref[base + 3, :], bk)


def _segment_agg_kernel(vals_ref, segs_ref, valid_ref, out_ref, *,
                        block_rows: int, block_segs: int, num_cols: int,
                        moments: tuple[tuple[str, ...], ...]):
    """Unpruned cross-product grid: (seg_tiles, row_blocks), rows fastest
    so the output tile stays VMEM-resident while every row block streams
    past it."""
    j = pl.program_id(0)          # segment tile (output stays resident)
    i = pl.program_id(1)          # row block   (streams past the tile)

    @pl.when(i == 0)
    def _():
        _init_out(out_ref, num_cols, block_segs, moments)

    _accum_rows(vals_ref, segs_ref, valid_ref, out_ref, j, i * block_rows,
                block_segs=block_segs, num_cols=num_cols, moments=moments)


def _segment_agg_kernel_pruned(rowm_ref, tilem_ref, nsteps_ref,
                               vals_ref, segs_ref, valid_ref, prev_ref,
                               out_ref, *, block_rows: int, block_segs: int,
                               num_cols: int,
                               moments: tuple[tuple[str, ...], ...]):
    """Band-pruned 1-D grid over one row range: step ``s`` works on row
    block ``rowm[s]`` and segment tile ``tilem[s]`` (scalar-prefetched
    maps; the BlockSpec index maps read the same arrays, so only
    intersecting blocks are fetched).  ``prev_ref`` is the output of the
    previous row range (identity fills before the first), aliased to
    ``out_ref``: a tile's first visit starts from it, so ranges chain.
    Steps past ``nsteps`` are grid padding — they repeat the last real
    (row_block, seg_tile) pair so no new DMA is issued, and the accumulate
    is gated off."""
    s = pl.program_id(0)
    j = tilem_ref[s]
    prev_j = tilem_ref[jnp.maximum(s - 1, 0)]

    @pl.when((s == 0) | (j != prev_j))    # first visit of this output tile
    def _():
        out_ref[...] = prev_ref[...]

    @pl.when(s < nsteps_ref[0])
    def _():
        _accum_rows(vals_ref, segs_ref, valid_ref, out_ref, j,
                    rowm_ref[s] * block_rows,
                    block_segs=block_segs, num_cols=num_cols,
                    moments=moments)


# ---------------------------------------------------------------------------
# Band computation (XLA-side, jit-safe) + host-side step accounting
# ---------------------------------------------------------------------------


def _band_maps(segs_flat: jax.Array, n_blocks: int, block_rows: int,
               block_segs: int, num_seg_tiles: int, grid_len: int):
    """Step→(row_block, seg_tile) maps for the pruned grid.

    Per-row-block tile bands [min_t, max_t] are flattened into one step
    sequence; for sorted input the bands are non-decreasing and overlap at
    most at endpoints, so the total real step count is bounded by
    ``n_blocks + num_seg_tiles - 1`` — the static ``grid_len``.  Steps
    beyond the real count clamp to the last real pair."""
    tiles = jnp.clip(segs_flat.reshape(n_blocks, block_rows) // block_segs,
                     0, num_seg_tiles - 1).astype(jnp.int32)
    min_t = jnp.min(tiles, axis=1)
    max_t = jnp.max(tiles, axis=1)
    spans = max_t - min_t + 1
    offs = jnp.concatenate([jnp.zeros((1,), jnp.int32),
                            jnp.cumsum(spans, dtype=jnp.int32)])
    nsteps = offs[-1]
    steps = jnp.arange(grid_len, dtype=jnp.int32)
    blk = jnp.clip(jnp.searchsorted(offs, steps, side="right") - 1,
                   0, n_blocks - 1).astype(jnp.int32)
    tile = jnp.clip(min_t[blk] + steps - offs[blk], min_t[blk], max_t[blk])
    return blk, tile.astype(jnp.int32), nsteps.astype(jnp.int32)


def pruned_grid_steps(segs, num_segments: int, block_rows: int = 256,
                      block_segs: int | None = None,
                      vmem_budget_elems: int = 1 << 19) -> int:
    """Executed-step count of the band-pruned kernel for concrete ``segs``
    (host-side numpy): the sum over row blocks of each block's segment-tile
    band span.  For sorted input this is at most
    ``row_blocks + seg_tiles - 1`` (the static pruned grid length) — vs the
    ``row_blocks × seg_tiles`` cross product of the unpruned grid (see
    ``full_grid_steps``).  Tests and benchmarks assert against it."""
    s = np.asarray(segs)
    if block_segs is None:
        block_segs = default_block_segs(num_segments, block_rows,
                                        vmem_budget_elems)
    pad = (-s.shape[0]) % block_rows
    if pad:
        # mirror _pad_rows: repeat the last real segment id so the final
        # row block's band is not widened to the end of the range
        last = s[-1] if s.shape[0] else 0
        s = np.concatenate([s, np.full(pad, last, s.dtype)])
    num_seg_tiles = -(-num_segments // block_segs)
    tiles = np.clip(s.reshape(-1, block_rows) // block_segs,
                    0, num_seg_tiles - 1)
    return int(np.sum(tiles.max(axis=1) - tiles.min(axis=1) + 1))


def full_grid_steps(n: int, num_segments: int, block_rows: int = 256,
                    block_segs: int | None = None,
                    vmem_budget_elems: int = 1 << 19) -> int:
    """Step count of the unpruned (seg_tiles × row_blocks) grid."""
    if block_segs is None:
        block_segs = default_block_segs(num_segments, block_rows,
                                        vmem_budget_elems)
    n_blocks = -(-n // block_rows)
    return n_blocks * -(-num_segments // block_segs)


def _range_blocks(num_seg_tiles: int) -> int:
    """Row blocks per band-pruned launch: the largest range whose static
    grid (range blocks + seg_tiles − 1) fits ``MAX_PREFETCH_STEPS``."""
    per = MAX_PREFETCH_STEPS - num_seg_tiles + 1
    if per < 1:
        raise ValueError(
            f"{num_seg_tiles} segment tiles exceed the {MAX_PREFETCH_STEPS} "
            f"grid steps one pruned launch can map — declare a smaller "
            f"group bound")
    return per


def launched_grid_steps(n: int, num_segments: int, block_rows: int = 256,
                        block_segs: int | None = None,
                        vmem_budget_elems: int = 1 << 19) -> int:
    """Static grid length ``fused_segment_agg`` actually launches for this
    shape: ``row_blocks`` when the segment range fits one tile (pruning is
    skipped — the row walk already is the whole grid), otherwise the
    band-pruned ``row_blocks + seg_tiles − 1`` per row range (which
    includes the padding steps past ``pruned_grid_steps``; padding repeats
    the last real block pair with the accumulate gated off), summed over
    the ranges ``MAX_PREFETCH_STEPS`` splits the rows into.  This is the
    number a dense group bound shrinks: ``seg_tiles`` is sized by
    ``num_segments``, so bounding it by the group count instead of the
    row capacity cuts the term — benchmarks/CI compare bounded vs
    capacity-sized launches."""
    if block_segs is None:
        block_segs = default_block_segs(num_segments, block_rows,
                                        vmem_budget_elems)
    n_blocks = -(-n // block_rows)
    num_seg_tiles = -(-num_segments // block_segs)
    if num_seg_tiles == 1:
        return n_blocks
    ranges = -(-n_blocks // _range_blocks(num_seg_tiles))
    return n_blocks + ranges * (num_seg_tiles - 1)


#: the sort-free route's cross-product grid may exceed the sorted route's
#: pruned grid by at most this factor — beyond it the group sort is the
#: cheaper way to the kernel (not yet measured on the chip; ROADMAP 1.5)
SORTFREE_GRID_RATIO = 4


def sortfree_grid_ok(n: int, num_segments: int,
                     block_rows: int = 256) -> bool:
    """Static route rule for the kernel backends: True when the sort-free
    (``layout='unsorted'``) cross-product grid over ``n`` rows stays
    within ``SORTFREE_GRID_RATIO`` × the band-pruned sorted grid.  A
    segment range that fits one tile always passes (both grids are the
    row walk); high-cardinality grouping — Q18's order keys at SF 10 are
    8k tiles — fails and takes the sorted route."""
    return (full_grid_steps(n, num_segments, block_rows)
            <= SORTFREE_GRID_RATIO
            * launched_grid_steps(n, num_segments, block_rows))


def moment_tensor_bytes(num_cols: int, num_segments: int) -> int:
    """Bytes of the (C, 4, num_segments) f32 moment tensor — the kernel
    output and the sharded path's all-reduce payload.  Sized by the static
    segment range, so a dense group bound shrinks it proportionally."""
    return num_cols * len(MOMENTS) * num_segments * 4


def _validate_sorted(segs, prune: bool, assume_sorted: bool,
                     backend: str) -> bool:
    """Shared sorted-``segs`` precondition check for the band-pruned kernel
    paths (single-device and sharded).  Only kernel backends with pruning
    active care — the jnp fallback and the unpruned grid are
    order-independent.  Concrete unsorted input raises eagerly; returns
    True when the caller still needs the traced runtime guard (NaN
    poison), False when the precondition is established."""
    if not prune or assume_sorted or backend not in ("pallas", "interpret"):
        return False
    if isinstance(segs, jax.core.Tracer):
        return True
    s_np = np.asarray(segs)
    if s_np.size > 1 and np.any(s_np[1:] < s_np[:-1]):
        raise ValueError(
            "fused_segment_agg: band pruning requires `segs` sorted "
            "ascending — sort rows by segment (the grouped executors do) "
            "or pass prune=False")
    return False


def _pad_rows(vals, segs, valid, block: int):
    """Pad the row dimension to a multiple of ``block``.  Pad rows are
    invalid (they never contribute) and repeat the LAST real segment id,
    which keeps ``segs`` monotone without widening the final row block's
    tile band to the end of the segment range — padding with
    ``num_segments`` would make the pruned grid walk every trailing tile."""
    n = vals.shape[0]
    pad = (-n) % block
    if not pad:
        return vals, segs, valid
    vals = jnp.pad(vals, ((0, pad), (0, 0)))
    last = segs[-1] if n else jnp.zeros((), segs.dtype)
    segs = jnp.concatenate([segs, jnp.full((pad,), last, segs.dtype)])
    valid = jnp.pad(valid, ((0, pad), (0, 0)))
    return vals, segs, valid


def resolve_backend(backend: str) -> str:
    """``'auto'`` → the compiled kernel on TPU, jnp segment ops elsewhere;
    any other name passes through."""
    if backend == "auto":
        return "pallas" if jax.default_backend() == "tpu" else "jnp"
    return backend


def _normalize(vals: jax.Array, valid: jax.Array) -> tuple[jax.Array, jax.Array]:
    """Lift (N,)/(N,C) vals and valid to matching (N, C)."""
    if vals.ndim == 1:
        vals = vals[:, None]
    if valid.ndim == 1:
        valid = valid[:, None]
    if valid.shape[1] == 1 and vals.shape[1] > 1:
        valid = jnp.broadcast_to(valid, vals.shape)
    return vals, valid


@functools.partial(jax.jit, static_argnames=("num_segments", "block_rows",
                                             "block_segs", "interpret",
                                             "moments", "prune",
                                             "check_sorted"))
def _segment_agg_pallas(vals: jax.Array, segs: jax.Array, valid: jax.Array,
                        num_segments: int, block_rows: int,
                        block_segs: int, interpret: bool,
                        moments: tuple[str, ...] = MOMENTS,
                        prune: bool = True,
                        check_sorted: bool = True) -> jax.Array:
    """(N, C) vals/valid → (C, R, num_segments) f32 via the Pallas kernel
    (R = 4 value-moment rows, 6 when any column requests an index
    moment)."""
    n, num_cols = vals.shape
    nrows = moment_rows(moments)
    vals, segs, valid = _pad_rows(vals, segs, valid, block_rows)
    n_p = vals.shape[0]
    segs = segs.astype(jnp.int32)
    # lane-dense operands: rows along lanes (see the module docstring)
    segs2 = segs.reshape(1, n_p)
    vals2 = vals.astype(jnp.float32).T
    valid2 = valid.astype(jnp.float32).T

    num_seg_tiles = -(-num_segments // block_segs)
    s_pad = num_seg_tiles * block_segs
    n_blocks = n_p // block_rows
    if num_seg_tiles == 1:
        prune = False       # single tile: the cross product IS the row walk
    out_rows = nrows * num_cols
    out_shape = jax.ShapeDtypeStruct((out_rows, s_pad), jnp.float32)

    if not prune:
        out = pl.pallas_call(
            functools.partial(_segment_agg_kernel, block_rows=block_rows,
                              block_segs=block_segs, num_cols=num_cols,
                              moments=moments),
            out_shape=out_shape,
            grid=(num_seg_tiles, n_blocks),
            in_specs=[
                pl.BlockSpec((num_cols, block_rows), lambda j, i: (0, i)),
                pl.BlockSpec((1, block_rows), lambda j, i: (0, i)),
                pl.BlockSpec((num_cols, block_rows), lambda j, i: (0, i)),
            ],
            out_specs=pl.BlockSpec((out_rows, block_segs),
                                   lambda j, i: (0, j)),
            interpret=interpret, name="segment_agg_unsorted",
        )(vals2, segs2, valid2)
        return out[:, :num_segments].reshape(num_cols, nrows, num_segments)

    # one launch per row range whose step maps fit SMEM; tiles no band
    # touches keep the identity fills the first range starts from
    kernel = functools.partial(_segment_agg_kernel_pruned,
                               block_rows=block_rows, block_segs=block_segs,
                               num_cols=num_cols, moments=moments)
    row_spec = pl.BlockSpec((num_cols, block_rows),
                            lambda s, rm, tm, ns: (0, rm[s]))
    tile_spec = pl.BlockSpec((out_rows, block_segs),
                             lambda s, rm, tm, ns: (0, tm[s]))
    out = jnp.broadcast_to(
        jnp.array(_row_fills(moments), jnp.float32)[:, None], out_shape.shape)
    per = _range_blocks(num_seg_tiles)
    for b0 in range(0, n_blocks, per):
        nb = min(per, n_blocks - b0)
        grid_len = nb + num_seg_tiles - 1
        rowm, tilem, nsteps = _band_maps(
            segs[b0 * block_rows:(b0 + nb) * block_rows], nb, block_rows,
            block_segs, num_seg_tiles, grid_len)
        grid_spec = pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=3,
            grid=(grid_len,),
            in_specs=[row_spec,
                      pl.BlockSpec((1, block_rows),
                                   lambda s, rm, tm, ns: (0, rm[s])),
                      row_spec, tile_spec],
            out_specs=tile_spec,
        )
        out = pl.pallas_call(
            kernel, out_shape=out_shape, grid_spec=grid_spec,
            input_output_aliases={6: 0}, interpret=interpret,
            name="segment_agg_sorted",
        )(rowm + b0, tilem, nsteps.reshape(1), vals2, segs2, valid2, out)

    if check_sorted:
        # pruning is only meaning-preserving on sorted segs; poison (rather
        # than silently mis-aggregate) when the precondition is violated
        # under tracing, where the eager check could not run
        is_sorted = jnp.all(segs[1:] >= segs[:-1]) if n_p > 1 else True
        out = jnp.where(is_sorted, out, jnp.float32(jnp.nan))
    return out[:, :num_segments].reshape(num_cols, nrows, num_segments)


_MOMENT_ROW = {"sum": 0, "count": 1, "min": 2, "max": 3}
_MOMENT_FILL = {"sum": 0.0, "count": 0.0, "min": POS_INF, "max": NEG_INF}


def _segment_arg_index_unsorted(key: jax.Array, idx_cand: jax.Array,
                                seg: jax.Array, num_segments: int, *,
                                minimize: bool,
                                tie_first: bool) -> jax.Array:
    """Per-segment attaining row index for ARBITRARY (unsorted) segment
    ids — the ``layout='unsorted'`` jnp formulation.  The associative-scan
    trick of ``_segment_arg_index_scan`` needs segment-contiguous rows, so
    this uses the hit-detection form instead: one segment extremum, one
    row-sized ``best[seg]`` gather (the single row-sized gather of the
    whole sort-free jnp route — still far below the sort it replaces),
    and a tie-ordered index reduce.  Invalid rows carry the worst key and
    the tie-identity index, so an empty segment's ``best`` (reduce
    identity) only ever "hits" rows that resolve to the tie identity —
    matching the sorted formulation bit for bit."""
    segf = jax.ops.segment_min if minimize else jax.ops.segment_max
    best = segf(key, seg, num_segments=num_segments)
    hit = key == jnp.take(best, seg, mode="clip")
    ident = POS_INF if tie_first else NEG_INF
    cand = jnp.where(hit, idx_cand, jnp.float32(ident))
    redf = jax.ops.segment_min if tie_first else jax.ops.segment_max
    # empty segments reduce to the tie identity (the redf identity IS the
    # tie identity for each order), so no extra emptiness gate is needed
    return redf(cand, seg, num_segments=num_segments)


def _segment_arg_index_scan(key: jax.Array, idx_cand: jax.Array,
                            seg: jax.Array, num_segments: int, *,
                            minimize: bool, tie_first: bool) -> jax.Array:
    """Per-segment attaining row index WITHOUT any row-sized gather.

    The classic jnp formulation (``key == best[seg]`` hit detection)
    issues an N-sized gather; instead this runs a segmented lexicographic
    reduce as one ``lax.associative_scan`` over (key, idx, seg) triples —
    contiguous sorted segments make the segment-reset combine associative
    — and reads each segment's result at its last row (an
    S-sized take).  ``idx_cand`` carries the tie identity (±inf) for
    invalid rows, so a valid row always beats an invalid one on equal
    keys.  Returns the f32 index row (tie identity for empty segments)."""
    n = key.shape[0]

    def combine(a, b):          # b is the later contiguous range
        ak, ai, as_ = a
        bk, bi, bs = b
        better = (bk < ak) if minimize else (bk > ak)
        i_better = (bi < ai) if tie_first else (bi > ai)
        take_b = (bs != as_) | better | ((bk == ak) & i_better)
        return (jnp.where(take_b, bk, ak), jnp.where(take_b, bi, ai), bs)

    _, red_idx, _ = lax.associative_scan(
        combine, (key, idx_cand, seg.astype(jnp.int32)))
    last = jax.ops.segment_max(jnp.arange(n, dtype=jnp.int32), seg,
                               num_segments=num_segments)
    got = last >= 0                           # segments with any row at all
    picked = jnp.take(red_idx, jnp.clip(last, 0, n - 1))
    ident = POS_INF if tie_first else NEG_INF
    return jnp.where(got, picked, jnp.float32(ident))


def _segment_agg_jnp(vals: jax.Array, segs: jax.Array, valid: jax.Array,
                     num_segments: int,
                     moments: tuple[tuple[str, ...], ...],
                     sorted_segs: bool = True) -> jax.Array:
    """Pure-JAX fallback, identical math: (N, C) → (C, R, num_segments).
    ``moments`` is per-column; moment rows a column does not request hold
    their init identity (0 / 0 / ±inf, tie identity for index rows).
    Unlike the kernel (where the fused pass makes extra moments nearly
    free), each jnp moment is a separate segment op, so it runs once per
    moment over exactly the columns that need it.  The value moments are
    order-independent (``jax.ops.segment_*`` scatter); only the index
    moments care about ``sorted_segs`` — contiguous sorted segments get
    the gather-free associative scan, arbitrary ids the hit-detection
    form."""
    v = vals.astype(jnp.float32)
    seg = segs.astype(jnp.int32)
    num_cols = vals.shape[1]
    nrows = moment_rows(moments)
    out = jnp.broadcast_to(
        jnp.asarray(_row_fills(moments),
                    jnp.float32).reshape(num_cols, nrows, 1),
        (num_cols, nrows, num_segments))
    for m in MOMENTS:
        idx = [c for c in range(num_cols) if m in moments[c]]
        if not idx:
            continue
        # static per-column slices, NOT v[:, idx] list-indexing: advanced
        # indexing lowers to an (N, len(idx)) gather, and this path is
        # spy-asserted to add no row-sized gathers beyond the group sort
        vi = jnp.stack([v[:, c] for c in idx], axis=1)
        gi = jnp.stack([valid[:, c] for c in idx], axis=1)
        if m == "sum":
            r = jax.ops.segment_sum(jnp.where(gi, vi, 0.0), seg,
                                    num_segments=num_segments)
        elif m == "count":
            r = jax.ops.segment_sum(gi.astype(jnp.float32), seg,
                                    num_segments=num_segments)
        elif m == "min":
            r = jax.ops.segment_min(jnp.where(gi, vi, POS_INF), seg,
                                    num_segments=num_segments)
        else:
            r = jax.ops.segment_max(jnp.where(gi, vi, NEG_INF), seg,
                                    num_segments=num_segments)
        out = out.at[jnp.asarray(idx), _MOMENT_ROW[m], :].set(r.T)
    if nrows == 6:
        n = vals.shape[0]
        rowidx = jnp.arange(n, dtype=jnp.float32)
        for c in range(num_cols):
            for which, row, minimize in (("argmin", ARGMIN_ROW, True),
                                         ("argmax", ARGMAX_ROW, False)):
                tie = _index_tie(moments[c], which)
                if tie is None:
                    continue
                worst = POS_INF if minimize else NEG_INF
                key = jnp.where(valid[:, c], v[:, c], worst)
                cand = jnp.where(valid[:, c], rowidx,
                                 POS_INF if tie else NEG_INF)
                argf = (_segment_arg_index_scan if sorted_segs
                        else _segment_arg_index_unsorted)
                r = argf(key, cand, seg, num_segments,
                         minimize=minimize, tie_first=tie)
                out = out.at[c, row, :].set(r)
    return out


def fused_segment_agg(vals: jax.Array, segs: jax.Array, valid: jax.Array,
                      num_segments: int, *, block_rows: int = 256,
                      block_segs: int | None = None,
                      backend: str = "auto",
                      moments: tuple[str, ...] = MOMENTS,
                      prune: bool = True,
                      assume_sorted: bool = False,
                      layout: str = "sorted") -> jax.Array:
    """Fused multi-column segmented aggregation.

    ``vals``  (N,) or (N, C) — C value columns over the same row stream.
    ``segs``  (N,) int in [0, num_segments); sorted ascending under the
    default ``layout='sorted'``, arbitrary under ``layout='unsorted'``.
    ``valid`` (N,) or (N, C) bool — per-column row validity (guards).
    This guard input is also how whole-plan fusion (relational/fuse.py)
    reaches the kernel: pushed-down Filter predicates and the join's
    found mask arrive pre-ANDed into ``valid`` rather than as a
    compacted row stream, and the fused chain's probe output arrives as
    ``segs`` (right-row indices under ``layout='unsorted'``) — no
    plumbing here is fusion-specific; the chain reuses these two
    arguments as-is.
    ``moments`` restricts which of [sum, count, min, max] (plus the
    optional index moments ``argmin_first``/``argmin_last``/
    ``argmax_first``/``argmax_last`` — see ``INDEX_MOMENTS``) are
    computed — either one tuple of moment names applied to every column,
    or a per-column tuple of tuples.  Skipped rows hold their init
    identity.  Requesting an index moment grows the output to 6 rows per
    column: rows 4/5 carry the f32 row index attaining the column's
    min/max with the requested tie order (tie identity ±inf for empty
    segments), and the padded row count must stay below 2^24 so f32
    represents every index exactly.

    ``prune`` (kernel backends only) enables band pruning: the compact
    O(row_blocks + seg_tiles) grid over exactly the (row_block, seg_tile)
    pairs whose bands intersect, instead of the full cross product.
    Pruning relies on the sorted-``segs`` precondition, which is
    *validated*, not assumed: concrete unsorted input raises ``ValueError``
    eagerly; traced input gets an O(N) runtime monotonicity guard that
    poisons the output with NaN on violation.  Callers that establish the
    order by construction (the grouped executors sort first) pass
    ``assume_sorted=True`` to skip both checks.

    ``layout='unsorted'`` is the sort-free grouped route's accumulation
    mode: segment ids may arrive in ANY order (hash-slotted, see
    relational/keyslot.py), so band pruning is disabled — the kernel
    backends run the order-independent cross-product grid (whose one-hot
    membership reduce never assumed an order; with a dense group bound
    the segment range fits one tile and the "cross product" degenerates
    to the plain row walk), the sorted-``segs`` validation is skipped
    outright, and the jnp index moments switch from the contiguity-
    dependent associative scan to the hit-detection form.  Every moment
    — including the lexicographic (key, row) index merge — is a
    commutative monoid, so results match the sorted layout exactly up to
    f32 re-association of sums.

    Returns (C, R, num_segments) f32 with moment rows [sum, count, min,
    max(, argmin-index, argmax-index)]; empty segments read the
    identities [0, 0, +inf, -inf(, ±inf, ±inf)].
    """
    if layout not in ("sorted", "unsorted"):
        raise ValueError(f"unknown segment_agg layout {layout!r}; expected "
                         "'sorted' or 'unsorted'")
    if layout == "unsorted":
        prune = False            # band pruning is meaningless out of order
    vals, valid = _normalize(jnp.asarray(vals), jnp.asarray(valid))
    num_cols = vals.shape[1]
    moments = normalize_moments(moments, num_cols)
    if has_index_moments(moments) and not index_moment_ok(vals.shape[0],
                                                          block_rows):
        raise ValueError(
            f"index moments accumulate f32 row indices, exact only "
            f"below 2^24 (padded) rows; got {vals.shape[0]} — split the "
            f"input or use the exact jnp arg path")
    backend = resolve_backend(backend)
    if backend == "jnp":
        return _segment_agg_jnp(vals, segs, valid, num_segments, moments,
                                sorted_segs=layout == "sorted")
    if backend not in ("pallas", "interpret"):
        raise ValueError(f"unknown segment_agg backend {backend!r}")
    if block_segs is None:
        block_segs = default_block_segs(num_segments, block_rows)
    check_sorted = (layout == "sorted"
                    and _validate_sorted(segs, prune, assume_sorted,
                                         backend))
    return _segment_agg_pallas(vals, jnp.asarray(segs), valid, num_segments,
                               block_rows, int(block_segs),
                               interpret=backend == "interpret",
                               moments=moments, prune=prune,
                               check_sorted=check_sorted)


def segment_agg(vals: jax.Array, segs: jax.Array, valid: jax.Array,
                num_segments: int, block_rows: int = 256,
                interpret: bool = True,
                block_segs: int | None = None) -> jax.Array:
    """Single-column legacy entry point: (4, num_segments) f32 rows
    [sum, count, min, max].  See ``fused_segment_agg`` for the
    multi-column / backend-dispatching API."""
    out = fused_segment_agg(vals, segs, valid, num_segments,
                            block_rows=block_rows, block_segs=block_segs,
                            backend="interpret" if interpret else "pallas")
    return out[0]
