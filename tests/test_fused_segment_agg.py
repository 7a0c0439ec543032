"""The fused grouped execution path (``mode='fused'``).

Four layers under test, all in Pallas interpret mode so CI needs no TPU:

1. the multi-column, segment-tiled kernel vs the pure-jnp oracle;
2. band pruning: the compact O(row_blocks + seg_tiles) grid executes the
   step count ``pruned_grid_steps`` predicts (ISSUE 2 acceptance bound on
   the sorted N=200k / S=8192 workload), matches the unpruned
   cross-product grid bit-for-bit, and validates the sorted-``segs``
   precondition instead of silently mis-aggregating;
3. grouped ``AggCall`` parity: ``mode='fused'`` must equal ``mode='stream'``
   (the sequential per-group semantics) on TPC-H-style grouped loops,
   including empty contributions, single-row segments, and segment counts
   exceeding one kernel tile;
4. the engine's built-in ``GroupAgg`` served from the fused kernel.
"""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import (Assign, BinOp, Col, Const, CursorLoop, If, Program,
                        Var, aggify, build_aggregate, fused_eligible, let,
                        run_rewritten)
from repro.core.executors import _resolve_grouped_mode
from repro.kernels import ref
from repro.kernels.segment_agg import (LANE, default_block_segs,
                                       full_grid_steps, fused_segment_agg,
                                       pruned_grid_steps, segment_agg)
from repro.relational import GroupAgg, Scan, Table, execute
from repro.relational.plan import AggCall, Filter

from helpers import fig1_program


# --------------------------------------------------------------------------
# 1. kernel: multi-column + segment tiling vs oracle
# --------------------------------------------------------------------------

@pytest.mark.parametrize("n,nseg,ncols,block_rows,block_segs", [
    (64, 8, 1, 16, 8),          # single column, single tile
    (200, 50, 3, 32, 16),       # 4 segment tiles
    (500, 300, 2, 128, 128),    # 3 tiles, wide segment range
    (100, 7, 4, 256, None),     # rows < block, default tile
])
def test_fused_kernel_vs_oracle(n, nseg, ncols, block_rows, block_segs):
    rng = np.random.default_rng(n * ncols + nseg)
    segs = np.sort(rng.integers(0, nseg, n)).astype(np.int32)
    vals = rng.uniform(-10, 10, (n, ncols)).astype(np.float32)
    valid = rng.random((n, ncols)) < 0.85
    got = fused_segment_agg(jnp.asarray(vals), jnp.asarray(segs),
                            jnp.asarray(valid), nseg, block_rows=block_rows,
                            block_segs=block_segs, backend="interpret")
    want = ref.fused_segment_agg_ref(jnp.asarray(vals), jnp.asarray(segs),
                                     jnp.asarray(valid), nseg)
    assert got.shape == (ncols, 4, nseg)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               rtol=1e-5, atol=1e-5)


def test_fused_kernel_jnp_backend_matches_interpret():
    rng = np.random.default_rng(3)
    n, nseg = 150, 40
    segs = np.sort(rng.integers(0, nseg, n)).astype(np.int32)
    vals = rng.uniform(-5, 5, (n, 2)).astype(np.float32)
    valid = rng.random((n, 2)) < 0.7
    a = fused_segment_agg(jnp.asarray(vals), jnp.asarray(segs),
                          jnp.asarray(valid), nseg, backend="jnp")
    b = fused_segment_agg(jnp.asarray(vals), jnp.asarray(segs),
                          jnp.asarray(valid), nseg, block_segs=16,
                          backend="interpret")
    np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                               rtol=1e-5, atol=1e-5)


def test_fused_kernel_per_column_masks():
    """Each column carries its own validity — differently-guarded updates
    share one pass but aggregate different row subsets."""
    segs = jnp.asarray(np.array([0, 0, 1, 1], np.int32))
    vals = jnp.asarray(np.array([[1., 10.], [2., 20.], [3., 30.], [4., 40.]],
                                np.float32))
    valid = jnp.asarray(np.array([[True, False], [True, True],
                                  [False, True], [True, True]]))
    out = np.asarray(fused_segment_agg(vals, segs, valid, 2,
                                       backend="interpret"))
    assert out[0, 0, 0] == 3.0 and out[0, 1, 0] == 2.0      # col0 seg0
    assert out[1, 0, 0] == 20.0 and out[1, 1, 0] == 1.0     # col1 seg0
    assert out[1, 2, 1] == 30.0 and out[1, 3, 1] == 40.0    # col1 seg1 min/max


def test_legacy_single_column_api_unchanged():
    segs = jnp.asarray(np.array([0, 0, 2, 2], np.int32))
    vals = jnp.asarray(np.array([1., 2., 3., 4.], np.float32))
    valid = jnp.asarray(np.array([True, True, False, False]))
    got = segment_agg(vals, segs, valid, 3, block_rows=4, interpret=True)
    assert got.shape == (3,) + () or got.shape == (4, 3)
    assert float(got[0, 0]) == 3.0
    assert float(got[1, 2]) == 0.0
    assert np.isinf(float(got[2, 2]))


def test_default_block_segs_alignment_and_budget():
    """Satellite invariants: every tile width is a multiple of the 128-lane
    VPU width (no ragged membership-mask reduces), at least one lane tile,
    at most the segment range rounded up to a lane multiple, and within
    the VMEM budget whenever the budget admits one lane group."""
    for nseg in (1, 10, 100, 512, 8192, 1 << 20):
        for br in (8, 128, 256, 1024, 4096):
            bs = default_block_segs(nseg, br)
            assert bs % LANE == 0
            assert bs >= LANE
            assert bs <= -(-nseg // LANE) * LANE      # lane-rounded range cap
    bs = default_block_segs(1 << 20, 256)
    assert bs * 256 <= 1 << 19                        # mask fits the budget
    assert default_block_segs(1 << 20, 4096) == LANE  # budget floor: 1 lane tile
    assert default_block_segs(10, 256) == LANE        # small ranges pad up


# --------------------------------------------------------------------------
# 2. band pruning: executed steps, parity, sorted-precondition guard
# --------------------------------------------------------------------------


def _sorted_workload(n, nseg, ncols=1, seed=2):
    rng = np.random.default_rng(seed)
    segs = np.sort(rng.integers(0, nseg, n)).astype(np.int32)
    vals = rng.uniform(-10, 10, (n, ncols)).astype(np.float32)
    valid = rng.random((n, ncols)) < 0.9
    return segs, vals, valid


def test_pruned_vs_unpruned_and_oracle_parity():
    """The pruned grid visits every intersecting (row_block, seg_tile)
    pair in the same order the cross-product grid does — same arithmetic,
    bit-identical output — while executing far fewer steps."""
    segs, vals, valid = _sorted_workload(5000, 600, ncols=3)
    kw = dict(block_rows=128, block_segs=128, backend="interpret")
    pr = fused_segment_agg(jnp.asarray(vals), jnp.asarray(segs),
                           jnp.asarray(valid), 600, **kw)
    un = fused_segment_agg(jnp.asarray(vals), jnp.asarray(segs),
                           jnp.asarray(valid), 600, prune=False, **kw)
    want = ref.fused_segment_agg_ref(jnp.asarray(vals), jnp.asarray(segs),
                                     jnp.asarray(valid), 600)
    assert np.array_equal(np.asarray(pr), np.asarray(un))
    np.testing.assert_allclose(np.asarray(pr), np.asarray(want),
                               rtol=1e-5, atol=1e-5)
    executed = pruned_grid_steps(segs, 600, 128, 128)
    full = full_grid_steps(5000, 600, 128, 128)
    assert executed <= (5000 // 128 + 1) + 2 * (600 // 128 + 1)
    assert executed * 3 < full


@pytest.mark.parametrize("moments", [
    ("sum", "count", "min", "max"),
    (("min", "argmin_first"), ("max", "argmax_last")),
])
def test_pruned_launch_splits_row_ranges(monkeypatch, moments):
    """A sorted input whose step maps exceed one launch's SMEM budget is
    split into row ranges chained through the aliased output: the result
    equals the single-launch and unpruned grids bit for bit, tiles no
    band touches keep their identities, and ``launched_grid_steps``
    counts every range's padding."""
    import importlib
    sa = importlib.import_module("repro.kernels.segment_agg")
    segs, vals, valid = _sorted_workload(5003, 900, ncols=2, seed=5)
    segs = np.where(segs > 700, segs + 150, segs)    # untouched tiles
    args = (jnp.asarray(vals), jnp.asarray(segs), jnp.asarray(valid), 1100)
    kw = dict(block_rows=128, block_segs=128, backend="interpret",
              moments=moments)
    whole = fused_segment_agg(*args, **kw)
    un = fused_segment_agg(*args, prune=False, **kw)
    monkeypatch.setattr(sa, "MAX_PREFETCH_STEPS", 24)   # 9 tiles: 16 blocks
    sa._segment_agg_pallas.clear_cache()
    try:
        split = fused_segment_agg(*args, **kw)
        steps = sa.launched_grid_steps(5003, 1100, 128, 128)
    finally:
        sa._segment_agg_pallas.clear_cache()
    assert steps == 40 + 3 * 8                       # 3 ranges of ≤16 blocks
    assert np.array_equal(np.asarray(split), np.asarray(whole))
    assert np.array_equal(np.asarray(split), np.asarray(un))


def test_pruned_grid_steps_acceptance_200k():
    """ISSUE 2 acceptance: a sorted N=200k / S=8192 workload executes at
    most row_blocks + 2·seg_tiles grid steps — vs the row_blocks ×
    seg_tiles cross product the unpruned grid walks."""
    n, nseg = 200_000, 8192
    rng = np.random.default_rng(42)
    segs = np.sort(rng.integers(0, nseg, n)).astype(np.int32)
    bs = default_block_segs(nseg, 256)
    row_blocks = -(-n // 256)
    seg_tiles = -(-nseg // bs)
    executed = pruned_grid_steps(segs, nseg, 256)
    assert executed <= row_blocks + 2 * seg_tiles
    assert full_grid_steps(n, nseg, 256) == row_blocks * seg_tiles
    assert executed * 3 < full_grid_steps(n, nseg, 256)


def test_pruned_interpret_parity_200k():
    """Acceptance workload under the interpreter: the band-pruned kernel
    == the unpruned kernel == the jnp oracle on N=200k / S=8192."""
    n, nseg = 200_000, 8192
    rng = np.random.default_rng(42)
    segs = jnp.asarray(np.sort(rng.integers(0, nseg, n)).astype(np.int32))
    vals = jnp.asarray(rng.uniform(-10, 10, n).astype(np.float32))
    valid = jnp.ones(n, bool)
    pr = fused_segment_agg(vals, segs, valid, nseg, backend="interpret")
    un = fused_segment_agg(vals, segs, valid, nseg, backend="interpret",
                           prune=False)
    want = fused_segment_agg(vals, segs, valid, nseg, backend="jnp")
    assert np.array_equal(np.asarray(pr), np.asarray(un))
    np.testing.assert_allclose(np.asarray(pr), np.asarray(want),
                               rtol=1e-4, atol=1e-4)


def test_pruned_unvisited_tiles_hold_identities():
    """Sparse segment use (all rows in segment 0 of a wide range): the
    pruned grid never visits most output tiles, which must still read the
    moment identities [0, 0, +inf, -inf], not uninitialized memory."""
    n, nseg = 256, 600
    vals = jnp.ones((n, 1), jnp.float32)
    segs = jnp.zeros(n, jnp.int32)
    out = np.asarray(fused_segment_agg(vals, segs, jnp.ones((n, 1), bool),
                                       nseg, backend="interpret",
                                       block_rows=128, block_segs=128))
    assert out[0, 0, 0] == n and out[0, 1, 0] == n
    assert np.all(out[0, 0, 1:] == 0) and np.all(out[0, 1, 1:] == 0)
    assert np.all(np.isposinf(out[0, 2, 1:]))
    assert np.all(np.isneginf(out[0, 3, 1:]))


def test_pruning_validates_sorted_precondition():
    """Unsorted segs under pruning: concrete input raises eagerly; traced
    input poisons the output with NaN (never a silently wrong aggregate);
    prune=False remains order-independent."""
    segs, vals, valid = _sorted_workload(400, 50)
    bad = segs[::-1].copy()
    kw = dict(block_rows=64, block_segs=16, backend="interpret")
    with pytest.raises(ValueError, match="sorted"):
        fused_segment_agg(jnp.asarray(vals), jnp.asarray(bad),
                          jnp.asarray(valid), 50, **kw)
    un = fused_segment_agg(jnp.asarray(vals), jnp.asarray(bad),
                           jnp.asarray(valid), 50, prune=False, **kw)
    want_bad = ref.fused_segment_agg_ref(jnp.asarray(vals),
                                         jnp.asarray(bad),
                                         jnp.asarray(valid), 50)
    np.testing.assert_allclose(np.asarray(un), np.asarray(want_bad),
                               rtol=1e-5, atol=1e-5)

    f = jax.jit(lambda s: fused_segment_agg(
        jnp.asarray(vals), s, jnp.asarray(valid), 50, **kw))
    assert np.all(np.isnan(np.asarray(f(jnp.asarray(bad)))))
    want = ref.fused_segment_agg_ref(jnp.asarray(vals), jnp.asarray(segs),
                                     jnp.asarray(valid), 50)
    np.testing.assert_allclose(np.asarray(f(jnp.asarray(segs))),
                               np.asarray(want), rtol=1e-5, atol=1e-5)


def test_assume_sorted_skips_guard():
    """Callers that sort by construction (the grouped executors) skip both
    the eager check and the traced NaN guard."""
    segs, vals, valid = _sorted_workload(300, 40)
    out = fused_segment_agg(jnp.asarray(vals), jnp.asarray(segs),
                            jnp.asarray(valid), 40, backend="interpret",
                            block_rows=64, block_segs=16,
                            assume_sorted=True)
    want = ref.fused_segment_agg_ref(jnp.asarray(vals), jnp.asarray(segs),
                                     jnp.asarray(valid), 40)
    np.testing.assert_allclose(np.asarray(out), np.asarray(want),
                               rtol=1e-5, atol=1e-5)


# --------------------------------------------------------------------------
# 3. grouped AggCall: fused == stream on TPC-H-style loops
# --------------------------------------------------------------------------


def _catalog(n=600, nparts=37, seed=0):
    rng = np.random.default_rng(seed)
    return {"PARTSUPP": Table.from_columns(
        ps_partkey=np.sort(rng.integers(0, nparts, n)).astype(np.int32),
        ps_suppkey=rng.integers(0, 100, n).astype(np.int32),
        ps_supplycost=rng.uniform(1, 100, n).astype(np.float32))}


_PS_SCHEMA = ("ps_partkey", "ps_suppkey", "ps_supplycost")


def _sum_count_prog():
    """Mean-style pattern: guarded sum + count (the mean decomposition)."""
    return Program(
        "sumCount", params=(),
        pre=[let("tot", Const(0.0)), let("cnt", Const(0.0))],
        loop=CursorLoop(
            Scan("PARTSUPP", _PS_SCHEMA),
            fetch=[("c", "ps_supplycost")],
            body=[If(Var("c") > Const(20.0),
                     [Assign("tot", Var("tot") + Var("c"))]),
                  Assign("cnt", Var("cnt") + Const(1.0))]),
        post=[], returns=("tot", "cnt"))


def _minmax_prog():
    return Program(
        "minMax", params=(),
        pre=[let("lo", Const(1e9)), let("hi", Const(-1e9))],
        loop=CursorLoop(
            Scan("PARTSUPP", _PS_SCHEMA),
            fetch=[("c", "ps_supplycost")],
            body=[Assign("lo", BinOp("min", Var("lo"), Var("c"))),
                  Assign("hi", BinOp("max", Var("hi"), Var("c")))]),
        post=[], returns=("lo", "hi"))


def _grouped_call(prog, mode, strip_filter=False):
    rp = aggify(prog)
    child = rp.agg_call.child
    if strip_filter:
        assert isinstance(child, Filter)
        child = child.child
    return AggCall(child, rp.agg_call.aggregate, rp.agg_call.param_binding,
                   rp.agg_call.ordered, rp.agg_call.sort_keys,
                   rp.agg_call.sort_desc, group_keys=("ps_partkey",),
                   mode=mode), rp


def _assert_grouped_parity(prog, env, cat, strip_filter=False,
                           monkeypatch=None):
    ref_call, _ = _grouped_call(prog, "stream", strip_filter)
    want = execute(ref_call, cat, env).to_numpy()
    fused_call, _ = _grouped_call(prog, "fused", strip_filter)
    got = execute(fused_call, cat, env).to_numpy()
    assert set(want) == set(got)
    for k in want:
        np.testing.assert_allclose(np.asarray(want[k], np.float32),
                                   np.asarray(got[k], np.float32),
                                   rtol=1e-5, atol=1e-5)
    return want


@pytest.mark.parametrize("backend", ["jnp", "interpret"])
def test_grouped_fused_parity_sum_count(backend, monkeypatch):
    monkeypatch.setenv("REPRO_SEGAGG_BACKEND", backend)
    env = {"tot": jnp.float32(0.0), "cnt": jnp.float32(0.0)}
    _assert_grouped_parity(_sum_count_prog(), env, _catalog())


@pytest.mark.parametrize("backend", ["jnp", "interpret"])
def test_grouped_fused_parity_minmax(backend, monkeypatch):
    monkeypatch.setenv("REPRO_SEGAGG_BACKEND", backend)
    env = {"lo": jnp.float32(1e9), "hi": jnp.float32(-1e9)}
    _assert_grouped_parity(_minmax_prog(), env, _catalog())


@pytest.mark.parametrize("backend", ["jnp", "interpret"])
def test_grouped_fused_parity_argmin_q2(backend, monkeypatch):
    """The paper's Figure-1 minCostSupp loop, decorrelated per part:
    arg_group key extremum from the kernel, payload gather on jnp."""
    monkeypatch.setenv("REPRO_SEGAGG_BACKEND", backend)
    rng = np.random.default_rng(5)
    n = 400
    cat = {
        "PARTSUPP": Table.from_columns(
            ps_partkey=np.sort(rng.integers(0, 23, n)).astype(np.int32),
            ps_suppkey=rng.integers(0, 40, n).astype(np.int32),
            ps_supplycost=rng.uniform(1, 50, n).astype(np.float32)),
        "SUPPLIER": Table.from_columns(
            s_suppkey=np.arange(40, dtype=np.int32),
            s_name=rng.permutation(40).astype(np.int32)),
    }
    env = {"lb": jnp.float32(4.0), "minCost": jnp.float32(100000.0),
           "suppName": jnp.int32(-1)}
    _assert_grouped_parity(fig1_program(), env, cat, strip_filter=True)


def test_grouped_fused_empty_contribution_groups(monkeypatch):
    """A guard that excludes every row of some groups: those segments must
    fall back to the pre-loop state (min identity +inf never leaks)."""
    monkeypatch.setenv("REPRO_SEGAGG_BACKEND", "interpret")
    n = 60
    rng = np.random.default_rng(9)
    cost = rng.uniform(1, 10, n).astype(np.float32)
    key = np.sort(rng.integers(0, 6, n)).astype(np.int32)
    cost[key % 2 == 0] = 5.0      # even groups never pass the >100 guard
    cat = {"PARTSUPP": Table.from_columns(
        ps_partkey=key, ps_suppkey=np.zeros(n, np.int32),
        ps_supplycost=cost)}
    prog = Program(
        "guardedMin", params=(),
        pre=[let("mn", Const(777.0))],
        loop=CursorLoop(
            Scan("PARTSUPP", _PS_SCHEMA),
            fetch=[("c", "ps_supplycost")],
            body=[If(Var("c") > Const(100.0),
                     [Assign("mn", BinOp("min", Var("mn"), Var("c")))])]),
        post=[], returns=("mn",))
    env = {"mn": jnp.float32(777.0)}
    out = _assert_grouped_parity(prog, env, cat)
    assert np.all(out["mn"] == 777.0)     # nothing ever passes the guard


def test_grouped_fused_single_row_segments(monkeypatch):
    monkeypatch.setenv("REPRO_SEGAGG_BACKEND", "interpret")
    n = 12
    cat = {"PARTSUPP": Table.from_columns(
        ps_partkey=np.arange(n, dtype=np.int32),            # every row its own group
        ps_suppkey=np.zeros(n, np.int32),
        ps_supplycost=np.linspace(1, 12, n).astype(np.float32))}
    env = {"tot": jnp.float32(0.0), "cnt": jnp.float32(0.0)}
    _assert_grouped_parity(_sum_count_prog(), env, cat)


def test_grouped_fused_segments_exceed_one_tile(monkeypatch):
    """More segments than one kernel tile: force 8-segment tiles over a
    90-group input so the grid walks 12 segment tiles."""
    monkeypatch.setenv("REPRO_SEGAGG_BACKEND", "interpret")
    import importlib
    sa = importlib.import_module("repro.kernels.segment_agg")
    monkeypatch.setattr(sa, "default_block_segs", lambda *a, **k: 8)
    env = {"lo": jnp.float32(1e9), "hi": jnp.float32(-1e9)}
    _assert_grouped_parity(_minmax_prog(), env,
                           _catalog(n=700, nparts=90, seed=11))


def test_fused_stream_parity_acceptance_workload(monkeypatch):
    """Acceptance workload at the engine level: grouped AggCall over 200k
    rows / 8192 groups, fused (band-pruned interpret kernel) == stream
    (the sequential segmented scan)."""
    monkeypatch.setenv("REPRO_SEGAGG_BACKEND", "interpret")
    env = {"tot": jnp.float32(0.0), "cnt": jnp.float32(0.0)}
    _assert_grouped_parity(_sum_count_prog(), env,
                           _catalog(n=200_000, nparts=8192, seed=13))


# --------------------------------------------------------------------------
# 4. mode selection + ungrouped fused + engine GroupAgg
# --------------------------------------------------------------------------


def test_auto_selects_fused_for_eligible_grouped():
    call, rp = _grouped_call(_sum_count_prog(), "auto")
    assert fused_eligible(rp.aggregate)
    assert _resolve_grouped_mode(call, rp.aggregate) == "fused"
    assert _resolve_grouped_mode(
        AggCall(call.child, call.aggregate, call.param_binding,
                group_keys=call.group_keys, mode="stream"),
        rp.aggregate) == "scan"


def test_fused_mode_rejects_unrecognized():
    """A data-dependent recurrence (cumulative product of state) has no
    moment decomposition — fused must refuse, stream must run."""
    prog = Program(
        "cumret", params=(),
        pre=[let("acc", Const(1.0))],
        loop=CursorLoop(
            Scan("PARTSUPP", _PS_SCHEMA),
            fetch=[("c", "ps_supplycost")],
            body=[Assign("acc", Var("acc") * (Var("acc") + Var("c")))]),
        post=[], returns=("acc",))
    agg = build_aggregate(prog)
    assert not fused_eligible(agg)
    call, _ = _grouped_call(prog, "fused")
    with pytest.raises(ValueError, match="fused"):
        execute(call, _catalog(), {"acc": jnp.float32(1.0)})


def test_ungrouped_fused_equals_stream():
    prog = _sum_count_prog()
    cat = _catalog()
    want = run_rewritten(aggify(prog), cat, {}, mode="stream")
    got = run_rewritten(aggify(prog), cat, {}, mode="fused")
    for k in want:
        np.testing.assert_allclose(np.asarray(want[k]), np.asarray(got[k]),
                                   rtol=1e-5)


def test_float64_fields_keep_exact_jnp_path():
    """The kernel accumulates in f32; with x64 enabled, f64 fields must
    route to the jnp segment path in their own dtype — a sum of values
    beyond f32's exact-integer range stays exact (run in a subprocess so
    the x64 flag cannot leak into other tests)."""
    import subprocess
    import sys
    code = """
import jax, numpy as np, jax.numpy as jnp
jax.config.update("jax_enable_x64", True)
from repro.core import Assign, Const, CursorLoop, Program, Var, aggify, let
from repro.relational import Scan, Table, execute
from repro.relational.plan import AggCall
big = float(2 ** 24)
cat = {"T": Table.from_columns(g=np.array([0, 0, 1], np.int32),
                               v=np.array([big, 1.0, 3.0], np.float64))}
prog = Program("s", params=(), pre=[let("acc", Const(0.0))],
               loop=CursorLoop(Scan("T", ("g", "v")), fetch=[("x", "v")],
                               body=[Assign("acc", Var("acc") + Var("x"))]),
               post=[], returns=("acc",), var_dtypes={"acc": jnp.float64})
rp = aggify(prog)
def call(mode):
    return AggCall(rp.agg_call.child, rp.agg_call.aggregate,
                   rp.agg_call.param_binding, group_keys=("g",), mode=mode)
out = execute(call("auto"), cat, {"acc": jnp.float64(0.0)}).to_numpy()
assert out["acc"].dtype == np.float64, out["acc"].dtype
assert out["acc"][0] == big + 1.0, out["acc"]          # f32 would round
# an explicit fused request over f64-only fields is refused, not silently
# downgraded to the kernel-free jnp pass
try:
    execute(call("fused"), cat, {"acc": jnp.float64(0.0)})
except ValueError as e:
    assert "f32" in str(e), e
else:
    raise AssertionError("mode='fused' over f64-only fields should raise")
print("OK")
"""
    r = subprocess.run([sys.executable, "-c", code], capture_output=True,
                       text=True, env={**__import__("os").environ,
                                       "JAX_PLATFORMS": "cpu"})
    assert r.returncode == 0 and "OK" in r.stdout, r.stderr


@pytest.mark.parametrize("backend", ["jnp", "interpret"])
def test_engine_groupagg_fused_parity(backend, monkeypatch):
    rng = np.random.default_rng(21)
    n = 300
    cat = {"L": Table.from_columns(
        k=np.sort(rng.integers(0, 19, n)).astype(np.int32),
        v=rng.uniform(-50, 50, n).astype(np.float32))}
    plan = GroupAgg(Scan("L", ("k", "v")), ("k",),
                    (("s", "sum", "v"), ("n", "count", None),
                     ("mn", "min", "v"), ("mx", "max", "v"),
                     ("avg", "mean", "v")))
    monkeypatch.setenv("REPRO_GROUPAGG_FUSED", "off")
    want = execute(plan, cat).to_numpy()
    monkeypatch.setenv("REPRO_GROUPAGG_FUSED", backend)
    got = execute(plan, cat).to_numpy()
    assert set(want) == set(got)
    for k in want:
        np.testing.assert_allclose(np.asarray(want[k], np.float32),
                                   np.asarray(got[k], np.float32),
                                   rtol=1e-5, atol=1e-4)
    assert got["n"].dtype == want["n"].dtype
