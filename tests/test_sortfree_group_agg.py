"""Sort-free grouped aggregation (relational/keyslot.py + the
``layout='unsorted'`` kernel mode + the sort-free dispatch in
engine.GroupAgg / grouped AggCall / launch.sharded_agg).

Covers: canonical key words and the quadratic-probe slotting (incl. a
degenerate constant hash — collisions are *resolved*, never assumed
away), overflow validation (concrete raise / traced poison), bit-for-bit
parity of the sort-free routes against the sorted ones over every
commutative op (built-in GroupAgg incl. argmin/argmax, fused and
recognized grouped AggCall, guarded empty-contribution groups, invalid
rows in the overflow slot), the unsorted kernel layout on the jnp AND
interpret backends, route dispatch (ordered calls and 'last' updates
stay sorted; the kill switch works), the structural sort census as a
tier-1 test, the variadic one-``lax.sort`` ``Table.sort_by`` satellite,
the stable ``_gather_join`` satellite, a subprocess 8-way-mesh run with
groups straddling shards, and the timing acceptance bound (sort-free
fused sum/count beats sorted on the bench shape).
"""
import os
import subprocess
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.relational import GroupAgg, Scan, Table, execute
from repro.relational.keyslot import (canonical_key_words,
                                      check_slot_overflow, key_words_for,
                                      slot_ids_from_words,
                                      slot_segment_ids)

AGGS = (("s", "sum", "v"), ("c", "count", None), ("mn", "min", "v"),
        ("mx", "max", "v"), ("avg", "mean", "v"), ("p", "prod", "v"),
        ("am", "argmin", ("v", "w")), ("ax", "argmax", ("v", "w")))


def _table(n, ngroups, seed=0, shuffle=True, invalid_every=0):
    """Integer-valued f32 values so every accumulation order is exact —
    the sort-free scatter order must then match the sorted segment order
    bit for bit."""
    rng = np.random.default_rng(seed)
    k = rng.integers(0, ngroups, n).astype(np.int32)
    if not shuffle:
        k = np.sort(k)
    t = Table.from_columns(
        k=k, v=rng.integers(-9, 9, n).astype(np.float32),
        w=rng.integers(0, 1000, n).astype(np.int32))
    if invalid_every:
        t = t.filter(jnp.asarray(np.arange(n) % invalid_every != 0))
    return t


def _aligned(t: Table, key: str = "k") -> dict:
    rows = t.to_numpy()
    order = np.argsort(rows[key], kind="stable")
    return {k: np.asarray(v)[order] for k, v in rows.items()}


def _both_routes(plan, cat, monkeypatch):
    monkeypatch.setenv("REPRO_GROUPAGG_SORTFREE", "off")
    want = _aligned(execute(plan, cat))
    monkeypatch.setenv("REPRO_GROUPAGG_SORTFREE", "on")
    got = _aligned(execute(plan, cat))
    return want, got


# --------------------------------------------------------------------------
# keyslot: canonical words + slotting
# --------------------------------------------------------------------------


def test_canonical_words_group_equality():
    w = key_words_for([
        jnp.asarray([0.0, -0.0, 1.5, np.nan, np.nan], jnp.float32),
        jnp.asarray([1, 1, 2, 3, 3], jnp.int32)])
    s, _, _, unpl = slot_ids_from_words(w, jnp.ones(5, bool), 128)
    s = np.asarray(s)
    assert int(unpl) == 0
    assert s[0] == s[1]                     # −0.0 groups with +0.0
    assert s[3] == s[4]                     # NaN keys share a bit-group
    assert len({int(s[0]), int(s[2]), int(s[3])}) == 3


def test_canonical_words_small_int_and_bool():
    for col in (jnp.asarray([-3, 0, 7, -3], jnp.int8),
                jnp.asarray([True, False, True, True]),
                jnp.asarray([1.5, -1.5, 1.5, 0.25], jnp.float16)):
        (w,) = canonical_key_words(col)
        assert w.dtype == jnp.uint32
        c = np.asarray(col)
        ww = np.asarray(w)
        for i in range(len(c)):
            for j in range(len(c)):
                assert (c[i] == c[j]) == (ww[i] == ww[j])


def test_slotting_same_key_same_slot_distinct_keys_distinct_slots():
    t = _table(4000, 150, seed=3)
    seg, owner, occ, unpl = map(np.asarray,
                                slot_segment_ids(t, ("k",), 256))
    assert unpl == 0
    k = np.asarray(t.columns["k"])
    slot_of = {}
    for i in range(len(k)):
        assert 0 <= seg[i] < 256
        slot_of.setdefault(int(k[i]), int(seg[i]))
        assert slot_of[int(k[i])] == seg[i]
    assert len(set(slot_of.values())) == len(slot_of)
    # dense claim-order prefix; owner rows really carry the slot's key
    assert occ.sum() == len(slot_of) and occ[:len(slot_of)].all()
    for key, s in slot_of.items():
        assert k[owner[s]] == key


def test_slotting_invalid_rows_park_in_overflow():
    n = 600
    t = Table({"k": jnp.asarray(np.arange(n, dtype=np.int32) % 40)},
              jnp.asarray(np.arange(n) % 3 == 0))
    seg, _, _, unpl = slot_segment_ids(t, ("k",), 128)
    seg = np.asarray(seg)
    assert int(unpl) == 0
    assert (seg[np.arange(n) % 3 != 0] == 128).all()
    assert (seg[np.arange(n) % 3 == 0] < 128).all()


def test_slotting_resolves_constant_hash_collisions(monkeypatch):
    """With EVERY key hashing identically, placement degenerates to pure
    quadratic probing — distinct keys must still land on distinct slots
    (collisions are resolved by key equality, not assumed away)."""
    import repro.relational.keyslot as ks
    monkeypatch.setattr(ks, "_hash_words",
                        lambda w: jnp.zeros(w.shape[:1], jnp.uint32))
    k = np.arange(64, dtype=np.int32).repeat(5)
    np.random.default_rng(0).shuffle(k)
    t = Table.from_columns(k=k)
    seg, owner, occ, unpl = map(np.asarray,
                                ks.slot_segment_ids(t, ("k",), 128))
    assert unpl == 0
    slots = {int(kk): int(ss) for kk, ss in zip(k, seg)}
    assert len(set(slots.values())) == 64
    assert occ.sum() == 64


def test_slotting_full_bucket_load():
    k = np.arange(128, dtype=np.int32).repeat(3)
    np.random.default_rng(1).shuffle(k)
    seg, _, occ, unpl = map(np.asarray, slot_segment_ids(
        Table.from_columns(k=k), ("k",), 128))
    assert unpl == 0 and occ.all() and len(np.unique(seg)) == 128


def test_slot_overflow_concrete_raises_traced_poisons():
    t = Table.from_columns(k=np.arange(200, dtype=np.int32),
                           v=np.ones(200, np.float32))
    plan = GroupAgg(Scan("T", ("k", "v")), ("k",),
                    (("s", "sum", "v"),), max_groups=100)
    with pytest.raises(ValueError, match="beyond the declared dense"):
        execute(plan, {"T": t})
    out = jax.jit(lambda tt: execute(plan, {"T": tt}))(t)
    assert np.isnan(np.asarray(out.columns["s"])).all()
    # and the guard helper itself
    assert check_slot_overflow(0, 128) is None
    with pytest.raises(ValueError):
        check_slot_overflow(5, 128)


# --------------------------------------------------------------------------
# built-in GroupAgg parity (sort-free vs sorted, aligned by key)
# --------------------------------------------------------------------------


@pytest.mark.parametrize("invalid_every", [0, 3])
def test_groupagg_sortfree_parity_all_ops(monkeypatch, invalid_every):
    monkeypatch.setenv("REPRO_GROUPAGG_FUSED", "jnp")
    t = _table(4000, 150, invalid_every=invalid_every)
    plan = GroupAgg(Scan("T", ("k", "v", "w")), ("k",), AGGS,
                    max_groups=150)
    want, got = _both_routes(plan, {"T": t}, monkeypatch)
    assert set(want) == set(got)
    for c in want:
        assert np.array_equal(want[c], got[c]), c


def test_groupagg_sortfree_parity_interpret_kernel(monkeypatch):
    """The exact Pallas lowering (interpret mode) under layout='unsorted'
    — the cross-product grid's one-hot reduce is order-independent."""
    monkeypatch.setenv("REPRO_GROUPAGG_FUSED", "interpret")
    t = _table(1500, 60, seed=5)
    plan = GroupAgg(Scan("T", ("k", "v", "w")), ("k",),
                    (("s", "sum", "v"), ("c", "count", None),
                     ("mn", "min", "v"), ("am", "argmin", ("v", "w"))),
                    max_groups=60)
    want, got = _both_routes(plan, {"T": t}, monkeypatch)
    for c in want:
        assert np.array_equal(want[c], got[c]), c


def test_groupagg_sortfree_multikey_and_float_keys(monkeypatch):
    monkeypatch.setenv("REPRO_GROUPAGG_FUSED", "jnp")
    rng = np.random.default_rng(7)
    n = 2000
    t = Table.from_columns(
        a=rng.integers(0, 8, n).astype(np.int32),
        b=(rng.integers(0, 7, n) * 0.5).astype(np.float32),
        v=rng.integers(-9, 9, n).astype(np.float32))
    plan = GroupAgg(Scan("T", ("a", "b", "v")), ("a", "b"),
                    (("s", "sum", "v"), ("c", "count", None)),
                    max_groups=64)
    monkeypatch.setenv("REPRO_GROUPAGG_SORTFREE", "off")
    w = execute(plan, {"T": t}).to_numpy()
    monkeypatch.setenv("REPRO_GROUPAGG_SORTFREE", "on")
    g = execute(plan, {"T": t}).to_numpy()
    wo = np.lexsort((w["b"], w["a"]))
    go = np.lexsort((g["b"], g["a"]))
    for c in w:
        assert np.array_equal(np.asarray(w[c])[wo], np.asarray(g[c])[go]), c


# --------------------------------------------------------------------------
# grouped AggCall (custom aggregates)
# --------------------------------------------------------------------------


def _grouped_call(prog, mode, max_groups):
    from repro.core import aggify
    from repro.relational.plan import AggCall
    rp = aggify(prog)
    return AggCall(rp.agg_call.child, rp.agg_call.aggregate,
                   rp.agg_call.param_binding, rp.agg_call.ordered,
                   rp.agg_call.sort_keys, rp.agg_call.sort_desc,
                   group_keys=("ps_partkey",), mode=mode,
                   max_groups=max_groups)


def _ps_catalog(n, ngroups, seed=0):
    rng = np.random.default_rng(seed)
    return {"PARTSUPP": Table.from_columns(
        ps_partkey=rng.integers(0, ngroups, n).astype(np.int32),
        ps_suppkey=rng.integers(0, 100, n).astype(np.int32),
        ps_supplycost=rng.integers(1, 100, n).astype(np.float32))}


@pytest.mark.parametrize("mode", ["fused", "recognized"])
@pytest.mark.parametrize("workload", ["sum_count", "minmax", "argmin"])
def test_agg_call_sortfree_parity(monkeypatch, mode, workload):
    from benchmarks.group_agg import _programs
    monkeypatch.setenv("REPRO_SEGAGG_BACKEND", "jnp")
    prog, env = _programs()[workload]
    cat = _ps_catalog(3000, 120, seed=2)
    call = _grouped_call(prog, mode, 120)
    monkeypatch.setenv("REPRO_GROUPAGG_SORTFREE", "off")
    want = _aligned(execute(call, cat, env), "ps_partkey")
    monkeypatch.setenv("REPRO_GROUPAGG_SORTFREE", "on")
    got = _aligned(execute(call, cat, env), "ps_partkey")
    for c in want:
        assert np.array_equal(want[c], got[c]), c


def test_agg_call_sortfree_guarded_empty_groups(monkeypatch):
    """A guard that excludes EVERY row of some groups: their outputs must
    fall back to the pre-loop state on both routes, bit for bit."""
    from repro.core import (Assign, Const, CursorLoop, If, Program, Var,
                            let)
    from benchmarks.group_agg import _programs  # noqa: F401  (idiom ref)
    monkeypatch.setenv("REPRO_SEGAGG_BACKEND", "jnp")
    scan = Scan("PARTSUPP", ("ps_partkey", "ps_suppkey", "ps_supplycost"))
    prog = Program(
        "guardedSum", params=(),
        pre=[let("tot", Const(-1.0))],
        loop=CursorLoop(scan, fetch=[("c", "ps_supplycost")],
                        body=[If(Var("c") > Const(90.0),
                                 [Assign("tot", Var("tot") + Var("c"))])]),
        post=[], returns=("tot",))
    cat = _ps_catalog(2000, 50, seed=3)
    env = {"tot": jnp.float32(-1.0)}
    call = _grouped_call(prog, "fused", 50)
    monkeypatch.setenv("REPRO_GROUPAGG_SORTFREE", "off")
    want = _aligned(execute(call, cat, env), "ps_partkey")
    monkeypatch.setenv("REPRO_GROUPAGG_SORTFREE", "on")
    got = _aligned(execute(call, cat, env), "ps_partkey")
    for c in want:
        assert np.array_equal(want[c], got[c]), c


# --------------------------------------------------------------------------
# dispatch: what fires sort-free and what must not
# --------------------------------------------------------------------------


def _slot_spy(monkeypatch):
    import repro.relational.keyslot as ks
    calls = []
    orig = ks.slot_segment_ids

    def spy(*a, **kw):
        calls.append(a)
        return orig(*a, **kw)

    monkeypatch.setattr(ks, "slot_segment_ids", spy)
    return calls


def test_sortfree_requires_declared_bound(monkeypatch):
    calls = _slot_spy(monkeypatch)
    t = _table(500, 20)
    execute(GroupAgg(Scan("T", ("k", "v", "w")), ("k",),
                     (("s", "sum", "v"),)), {"T": t})
    assert not calls                      # no bound declared -> sorted
    execute(GroupAgg(Scan("T", ("k", "v", "w")), ("k",),
                     (("s", "sum", "v"),), max_groups=20), {"T": t})
    assert len(calls) == 1


@pytest.mark.parametrize("fused,sortfree", [("jnp", True),
                                            ("interpret", False)])
def test_kernel_grid_rule_picks_sorted_route(monkeypatch, fused, sortfree):
    """High-cardinality grouping under a kernel backend: the unsorted
    cross-product grid (9 tiles × 79 row blocks) dwarfs the pruned sorted
    grid, so GroupAgg and grouped AggCall sort instead — jnp segment ops
    have no grid and stay sort-free.  Both routes give the same groups."""
    from repro.kernels.segment_agg import sortfree_grid_ok
    assert sortfree_grid_ok(4096, 513) and not sortfree_grid_ok(20000, 16385)
    calls = _slot_spy(monkeypatch)
    monkeypatch.setenv("REPRO_GROUPAGG_FUSED", fused)
    monkeypatch.setenv("REPRO_SEGAGG_BACKEND", fused)
    t = _table(20000, 10000, seed=9)
    plan = GroupAgg(Scan("T", ("k", "v", "w")), ("k",),
                    (("s", "sum", "v"), ("mx", "max", "w")),
                    max_groups=10000)
    got = _aligned(execute(plan, {"T": t}), "k")
    from benchmarks.group_agg import _programs
    prog, env = _programs()["sum_count"]
    call = _grouped_call(prog, "fused", 10000)
    got_call = _aligned(execute(call, _ps_catalog(20000, 10000, seed=4),
                                env), "ps_partkey")
    assert len(calls) == (2 if sortfree else 0)
    monkeypatch.setenv("REPRO_GROUPAGG_SORTFREE", "off")
    want = _aligned(execute(plan, {"T": t}), "k")
    want_call = _aligned(execute(call, _ps_catalog(20000, 10000, seed=4),
                                 env), "ps_partkey")
    for w, g in ((want, got), (want_call, got_call)):
        for c in w:
            np.testing.assert_allclose(w[c], g[c], rtol=1e-6, err_msg=c)


def test_sortfree_kill_switch(monkeypatch):
    calls = _slot_spy(monkeypatch)
    monkeypatch.setenv("REPRO_GROUPAGG_SORTFREE", "off")
    t = _table(500, 20)
    execute(GroupAgg(Scan("T", ("k", "v", "w")), ("k",),
                     (("s", "sum", "v"),), max_groups=20), {"T": t})
    assert not calls


def test_ordered_agg_call_stays_sorted(monkeypatch):
    """Eq.-6 ordered invocation (the fig-2 running-product shape) must
    keep the sorted route: its semantics depend on the iteration order."""
    from repro.core import aggify
    from repro.relational.plan import AggCall
    from tests.helpers import fig2_catalog, fig2_program
    calls = _slot_spy(monkeypatch)
    prog = fig2_program()
    rp = aggify(prog)
    call = AggCall(rp.agg_call.child, rp.agg_call.aggregate,
                   rp.agg_call.param_binding, rp.agg_call.ordered,
                   rp.agg_call.sort_keys, rp.agg_call.sort_desc,
                   group_keys=("investor_id",), mode="auto", max_groups=8)
    out = execute(call, fig2_catalog(),
                  {"id": jnp.int32(1), "cumulativeROI": jnp.float32(1.0)})
    assert not calls                      # ordered -> never sort-free
    assert out.capacity > 0


def test_sortfree_sort_census_tier1():
    """Tier-1 face of the CI spy: the sort-free lowering of the grouped
    bench programs contains ZERO row-sized sorts, the sorted route at
    least one and no row-sized gather (its columns ride through the
    sort), and sort-free adds no row-sized gathers."""
    from benchmarks.sortfree_spy import sortfree_census
    counts = sortfree_census(2_000, 64, "jnp")
    for name, c in counts.items():
        assert c["row_sorts_sortfree"] == 0, (name, c)
        assert c["row_sorts_sorted"] >= 1, (name, c)
        assert c["row_gathers_sorted"] == 0, (name, c)
        assert c["row_gathers_sortfree"] <= c["row_gathers_sorted"], \
            (name, c)


# --------------------------------------------------------------------------
# kernel layout='unsorted'
# --------------------------------------------------------------------------


def _unsorted_workload(n, nseg, seed=11):
    rng = np.random.default_rng(seed)
    segs = rng.integers(0, nseg, n).astype(np.int32)      # NOT sorted
    vals = rng.integers(-50, 50, (n, 2)).astype(np.float32)
    valid = rng.random((n, 2)) < 0.8
    return segs, vals, valid


@pytest.mark.parametrize("backend", ["jnp", "interpret"])
def test_kernel_unsorted_layout_matches_sorted_oracle(backend):
    from repro.kernels.segment_agg import fused_segment_agg
    segs, vals, valid = _unsorted_workload(3000, 97)
    order = np.argsort(segs, kind="stable")
    moms = (("sum", "count", "min", "max", "argmin_first"),
            ("sum", "max", "argmax_last"))
    got = fused_segment_agg(vals, segs, valid, 97, moments=moms,
                            layout="unsorted", backend=backend)
    want = fused_segment_agg(vals[order], segs[order], valid[order], 97,
                             moments=moms, backend="jnp")
    got, want = np.asarray(got), np.asarray(want)
    assert np.array_equal(got[:, :4], want[:, :4])
    # index rows: sorted-space indices map back through the permutation
    for c, row in ((0, 4), (1, 5)):
        for g in range(97):
            w = want[c, row, g]
            if np.isfinite(w):
                assert order[int(w)] == int(got[c, row, g]), (c, g)
            else:
                assert w == got[c, row, g]


def test_kernel_unsorted_layout_skips_sorted_validation():
    from repro.kernels.segment_agg import fused_segment_agg
    segs, vals, valid = _unsorted_workload(800, 40)
    # sorted layout rejects concrete unsorted input; unsorted accepts it
    with pytest.raises(ValueError, match="sorted"):
        fused_segment_agg(vals, segs, valid, 40, backend="interpret")
    out = fused_segment_agg(vals, segs, valid, 40, backend="interpret",
                            layout="unsorted")
    assert np.isfinite(np.asarray(out)[:, 0]).all()
    with pytest.raises(ValueError, match="layout"):
        fused_segment_agg(vals, segs, valid, 40, layout="diagonal")


# --------------------------------------------------------------------------
# satellites: variadic sort_by + stable join pick
# --------------------------------------------------------------------------


def test_sort_by_is_one_variadic_sort():
    from repro.analysis.jaxpr_spy import (count_row_sized_gathers,
                                          sort_output_sizes)
    t = _table(1000, 30, invalid_every=4)
    for keys, desc in ((["k"], ()), (["k", "v"], [False, True]),
                       (["k", "v", "w"], [True, False, False])):
        j = jax.make_jaxpr(
            lambda ks=keys, d=desc: tuple(
                t.sort_by(ks, d).columns.values()))()
        assert len(sort_output_sizes(j)) == 1, keys
        # 1-D columns ride through the sort: no gather by its permutation
        assert count_row_sized_gathers(j, t.capacity) == 0, keys


def _sort_table(n, valid, seed=4):
    """Columns of every kind the sorted route carries, with ties, NaN,
    -0.0 and infinities among the floats, and one (n, 2) column that
    takes the gathered fallback."""
    rng = np.random.default_rng(seed)
    floats = np.array([np.nan, -0.0, 0.0, np.inf, -np.inf, 1.5, -2.0,
                       3.25], np.float32)
    cols = {"i32": rng.integers(-20, 20, n).astype(np.int32),
            "f32": rng.choice(floats, n),
            "b": rng.random(n) < 0.5,
            "i8": rng.integers(-3, 4, n).astype(np.int8),
            "pair": rng.integers(0, 1000, (n, 2)).astype(np.int32)}
    m = (rng.random(n) < 0.7) if valid else np.ones(n, bool)
    t = Table({k: jnp.asarray(v) for k, v in cols.items()},
              jnp.asarray(m) if valid else None)
    return t, cols, m


def _lexsort_oracle(cols, m, keys, desc):
    sk = []
    for k, d in zip(keys, desc):
        c = cols[k].astype(np.int32) if cols[k].dtype == bool else cols[k]
        c = -c if d else c
        big = (np.inf if np.issubdtype(c.dtype, np.floating)
               else np.iinfo(c.dtype).max)
        sk.append(np.where(m, c, big))
    return np.lexsort((np.arange(len(m)),) + tuple(sk[::-1]) + (~m,))


@pytest.mark.parametrize("keys,desc,valid", [
    (["i32"], [False], False),
    (["f32"], [True], True),
    (["b"], [False], True),
    (["i8"], [True], False),
    (["i8", "f32"], [True, False], False),
    (["i32", "f32"], [False, True], True),
    (["b", "i8", "f32"], [False, True, True], True),
    (["f32", "i32", "b"], [True, True, False], False),
])
def test_sort_by_parity_with_lexsort_oracle(keys, desc, valid):
    from jax.sharding import Mesh
    n = 1000
    t, cols, m = _sort_table(n, valid)
    mesh = Mesh(np.asarray(jax.devices()[:1]), ("data",))
    t = t.declare_group_bound(64)
    t = Table(t.columns, t.valid, t.group_bound, row_split=(mesh, "data"))
    st = t.sort_by(keys, desc)
    order = _lexsort_oracle(cols, m, keys, desc)
    assert list(st.columns) == list(cols)
    for k, c in cols.items():
        got = np.asarray(st.columns[k])
        assert got.dtype == c.dtype and got.shape == c.shape, k
        assert np.array_equal(got.view(np.uint8), c[order].view(np.uint8)), k
    assert np.array_equal(np.asarray(st.mask()), m[order])
    assert st.group_bound == t.group_bound == 128
    assert st.row_split == (mesh, "data")


def test_gather_join_duplicate_right_keys_deterministic():
    """_gather_join is documented for unique right keys; with duplicates
    the stable sort must make the pick deterministic: the smallest
    original right row among equal keys."""
    from repro.relational.engine import _gather_join
    lt = Table.from_columns(x=np.array([7, 8], np.int32))
    rt = Table.from_columns(
        x=np.array([8, 7, 7, 8, 7], np.int32),
        y=np.array([100, 101, 102, 103, 104], np.int32))
    out = _gather_join(lt, rt, "x", "x", "inner")
    assert np.array_equal(np.asarray(out.columns["y"]), [101, 100])


# --------------------------------------------------------------------------
# sharded: subprocess 8-way mesh, groups straddling shards
# --------------------------------------------------------------------------


def test_sharded_sortfree_in_subprocess_8way_mesh():
    code = """
import os, numpy as np, jax, jax.numpy as jnp
os.environ["REPRO_GROUPAGG_FUSED"] = "jnp"
assert jax.device_count() == 8, jax.device_count()
from jax.sharding import Mesh
from repro.relational import GroupAgg, Scan, Table, execute

mesh = Mesh(np.asarray(jax.devices()).reshape(8), ("data",))
rng = np.random.default_rng(9)
n, ng = 4096, 60
t = Table.from_columns(
    k=rng.integers(0, ng, n).astype(np.int32),   # unsorted: every group straddles shards
    v=rng.integers(-40, 40, n).astype(np.float32),
    p=rng.integers(0, 1000, n).astype(np.int32))
plan = GroupAgg(Scan("L", ("k", "v", "p")), ("k",),
                (("s", "sum", "v"), ("c", "count", None),
                 ("mn", "min", "v"), ("mx", "max", "v"),
                 ("am", "argmin", ("v", "p"))), max_groups=ng)
os.environ["REPRO_GROUPAGG_SORTFREE"] = "off"
want = execute(plan, {"L": t}).to_numpy()
os.environ.pop("REPRO_GROUPAGG_SORTFREE")

import repro.launch.sharded_agg as sa
calls = []
orig = sa.sharded_sortfree_segment_agg
def spy(*a, **kw):
    calls.append(a[4])
    return orig(*a, **kw)
sa.sharded_sortfree_segment_agg = spy
out = execute(plan, {"L": t.shard_rows(mesh, "data")})
got = out.to_numpy()
assert calls == [129], calls          # bucket(60) -> 128-lane floor + overflow
assert out.capacity == 129
ws, gs = np.argsort(want["k"]), np.argsort(got["k"])
for c in want:
    assert np.array_equal(np.asarray(want[c])[ws], np.asarray(got[c])[gs]), c

# cross-shard tie: one giant all-tying group -> first-attaining row wins
t2 = Table.from_columns(k=np.zeros(4096, np.int32),
                        v=np.full(4096, 7.0, np.float32),
                        p=np.arange(4096).astype(np.int32))
plan2 = GroupAgg(Scan("L", ("k", "v", "p")), ("k",),
                 (("am", "argmin", ("v", "p")),), max_groups=2)
g2 = execute(plan2, {"L": t2.shard_rows(mesh, "data")}).to_numpy()
assert g2["am"][0] == 0, g2["am"]
print("OK")
"""
    src = os.path.join(os.path.dirname(__file__), "..", "src")
    env = {**os.environ,
           "JAX_PLATFORMS": "cpu",
           "XLA_FLAGS": (os.environ.get("XLA_FLAGS", "") +
                         " --xla_force_host_platform_device_count=8"),
           "PYTHONPATH": os.path.abspath(src) + os.pathsep +
                         os.environ.get("PYTHONPATH", "")}
    r = subprocess.run([sys.executable, "-c", code], capture_output=True,
                       text=True, env=env)
    assert r.returncode == 0 and "OK" in r.stdout, r.stderr


# --------------------------------------------------------------------------
# acceptance: sort-free fused sum/count beats the sorted fused path
# --------------------------------------------------------------------------


def test_sortfree_beats_sorted_fused_sum_count(monkeypatch):
    """The bench-shape acceptance bound (also a CI gate on the fresh
    bench artifact): same bounded fused sum/count GroupAgg, route pinned
    sorted vs sort-free — deleting the sort must win wall-clock."""
    from benchmarks.group_agg import _catalog
    from benchmarks.util import time_fn
    monkeypatch.setenv("REPRO_GROUPAGG_FUSED", "jnp")
    n, ng = 50_000, 512
    cat = _catalog(n, ng)
    plan = GroupAgg(Scan("PARTSUPP",
                         ("ps_partkey", "ps_suppkey", "ps_supplycost")),
                    ("ps_partkey",),
                    (("s", "sum", "ps_supplycost"), ("c", "count", None)),
                    max_groups=ng)

    def timed():
        fn = jax.jit(lambda: execute(plan, cat))
        return time_fn(lambda: fn().columns, repeats=5, warmup=2)

    monkeypatch.setenv("REPRO_GROUPAGG_SORTFREE", "off")
    us_sorted = timed()
    monkeypatch.setenv("REPRO_GROUPAGG_SORTFREE", "on")
    us_free = timed()
    assert us_free < us_sorted, (us_free, us_sorted)
