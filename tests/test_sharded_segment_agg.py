"""The mesh-sharded fused segmented-aggregation path (launch/sharded_agg.py).

Two tiers:

* **Direct tests** need an 8-way host mesh
  (``XLA_FLAGS=--xla_force_host_platform_device_count=8`` — the CI
  multi-device step sets it before jax initializes); on a single-device
  run they skip.  They cover kernel-level parity (bitwise for
  integer-valued f32 data, where shard-boundary re-association is exact),
  segments straddling shard boundaries, empty shards, the
  ``shard_merge``-fold ↔ collective-merge equivalence, and the transparent
  ``GroupAgg`` / grouped ``AggCall`` routing for a ``Table.shard_rows``
  input.
* **A subprocess test** keeps the same coverage in plain tier-1 (one
  device): it spawns an interpreter with the flag and asserts the
  end-to-end parity + routing there.
"""
import os
import subprocess
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.launch.sharded_agg import row_sharded_mesh

needs_mesh = pytest.mark.skipif(
    jax.device_count() < 8,
    reason="needs XLA_FLAGS=--xla_force_host_platform_device_count=8")


@pytest.fixture(scope="module")
def mesh():
    from jax.sharding import Mesh
    return Mesh(np.asarray(jax.devices()[:8]).reshape(8), ("data",))


def _sorted_int_workload(n, nseg, ncols=1, seed=7):
    """Integer-valued f32 data: every summation order is exact, so the
    sharded merge must match the single-device kernel bit-for-bit."""
    rng = np.random.default_rng(seed)
    segs = np.sort(rng.integers(0, nseg, n)).astype(np.int32)
    vals = rng.integers(-50, 50, (n, ncols)).astype(np.float32)
    valid = rng.random((n, ncols)) < 0.8
    return segs, vals, valid


# --------------------------------------------------------------------------
# detection (runs on any device count)
# --------------------------------------------------------------------------


def test_row_sharded_mesh_ignores_unsharded_and_none():
    assert row_sharded_mesh(jnp.arange(8), None) is None


def test_row_sharded_mesh_kill_switch(monkeypatch, mesh=None):
    monkeypatch.setenv("REPRO_SEGAGG_SHARDED", "off")
    assert row_sharded_mesh(jnp.arange(8)) is None


@needs_mesh
def test_row_sharded_mesh_detects_committed_rows(mesh):
    from jax.sharding import NamedSharding, PartitionSpec as P
    a = jax.device_put(jnp.arange(64, dtype=jnp.float32),
                       NamedSharding(mesh, P("data")))
    got = row_sharded_mesh(a)
    assert got is not None and got[1] == "data"
    # replicated arrays don't route
    b = jax.device_put(jnp.arange(64, dtype=jnp.float32),
                       NamedSharding(mesh, P()))
    assert row_sharded_mesh(b) is None


# --------------------------------------------------------------------------
# kernel-level parity on the 8-way mesh
# --------------------------------------------------------------------------


@needs_mesh
def test_sharded_kernel_bitwise_parity(mesh):
    from repro.kernels.segment_agg import fused_segment_agg
    from repro.launch.sharded_agg import sharded_fused_segment_agg
    segs, vals, valid = _sorted_int_workload(4096, 300, ncols=2)
    single = fused_segment_agg(jnp.asarray(vals), jnp.asarray(segs),
                               jnp.asarray(valid), 300, backend="jnp")
    shd = sharded_fused_segment_agg(jnp.asarray(vals), jnp.asarray(segs),
                                    jnp.asarray(valid), 300, mesh=mesh,
                                    axis="data", backend="jnp")
    assert np.array_equal(np.asarray(single), np.asarray(shd))


@needs_mesh
def test_sharded_interpret_kernel_per_shard(mesh):
    """The band-pruned Pallas kernel (interpret mode) runs inside
    shard_map: each shard's contiguous sorted slice keeps the pruning
    precondition."""
    from repro.kernels.segment_agg import fused_segment_agg
    from repro.launch.sharded_agg import sharded_fused_segment_agg
    segs, vals, valid = _sorted_int_workload(2048, 300)
    single = fused_segment_agg(jnp.asarray(vals), jnp.asarray(segs),
                               jnp.asarray(valid), 300, backend="jnp")
    shd = sharded_fused_segment_agg(jnp.asarray(vals), jnp.asarray(segs),
                                    jnp.asarray(valid), 300, mesh=mesh,
                                    axis="data", backend="interpret",
                                    block_rows=128, block_segs=128)
    np.testing.assert_allclose(np.asarray(shd), np.asarray(single),
                               rtol=1e-5, atol=1e-5)


@needs_mesh
def test_segments_straddle_shard_boundaries(mesh):
    """One giant segment spanning every shard + per-row segments at the
    tail: the psum/pmin/pmax merge must reassemble both shapes."""
    from repro.kernels.segment_agg import fused_segment_agg
    from repro.launch.sharded_agg import sharded_fused_segment_agg
    n = 64
    segs = np.concatenate([np.zeros(40, np.int32),
                           np.arange(1, 25, dtype=np.int32)])
    vals = np.arange(n, dtype=np.float32)[:, None]
    valid = np.ones((n, 1), bool)
    single = fused_segment_agg(jnp.asarray(vals), jnp.asarray(segs),
                               jnp.asarray(valid), 25, backend="jnp")
    shd = sharded_fused_segment_agg(jnp.asarray(vals), jnp.asarray(segs),
                                    jnp.asarray(valid), 25, mesh=mesh,
                                    axis="data", backend="jnp")
    assert np.array_equal(np.asarray(single), np.asarray(shd))


@needs_mesh
def test_empty_and_uneven_shards(mesh):
    """n=9 rows over 8 shards: padding fills the tail shards with invalid
    rows, which must contribute exactly the moment identities."""
    from repro.kernels.segment_agg import fused_segment_agg
    from repro.launch.sharded_agg import sharded_fused_segment_agg
    rng = np.random.default_rng(11)
    n = 9
    segs = np.sort(rng.integers(0, 5, n)).astype(np.int32)
    vals = rng.integers(0, 10, (n, 1)).astype(np.float32)
    single = fused_segment_agg(jnp.asarray(vals), jnp.asarray(segs),
                               jnp.ones((n, 1), bool), 5, backend="jnp")
    shd = sharded_fused_segment_agg(jnp.asarray(vals), jnp.asarray(segs),
                                    jnp.ones((n, 1), bool), 5, mesh=mesh,
                                    axis="data", backend="jnp")
    assert np.array_equal(np.asarray(single), np.asarray(shd))


@needs_mesh
def test_shard_merge_fold_matches_collective_merge(mesh):
    """moment_merge_aggregate under core.aggregate.shard_merge (all-gather
    + ordered fold) == the native psum/pmin/pmax merge — the sharded path
    really is the shard_merge algebra."""
    from jax.sharding import PartitionSpec as P

    from repro.core.aggregate import shard_merge
    from repro.kernels.segment_agg import fused_segment_agg
    from repro.launch.sharded_agg import (moment_merge_aggregate,
                                          sharded_fused_segment_agg)
    segs, vals, valid = _sorted_int_workload(4096, 128, ncols=2)
    locals_ = [
        fused_segment_agg(jnp.asarray(vals[i * 512:(i + 1) * 512]),
                          jnp.asarray(segs[i * 512:(i + 1) * 512]),
                          jnp.asarray(valid[i * 512:(i + 1) * 512]),
                          128, backend="jnp")
        for i in range(8)]
    agg = moment_merge_aggregate(2, 128)

    def fold(loc):
        return shard_merge(agg, loc[0], "data")

    folded = jax.shard_map(fold, mesh=mesh, in_specs=(P("data"),),
                           out_specs=P(), check_vma=False)(
                               jnp.stack(locals_))
    shd = sharded_fused_segment_agg(jnp.asarray(vals), jnp.asarray(segs),
                                    jnp.asarray(valid), 128, mesh=mesh,
                                    axis="data", backend="jnp")
    assert np.array_equal(np.asarray(folded), np.asarray(shd))


# --------------------------------------------------------------------------
# transparent engine routing
# --------------------------------------------------------------------------


def _route_counter(monkeypatch):
    import repro.launch.sharded_agg as sa
    calls = []
    orig = sa.sharded_fused_segment_agg

    def spy(*a, **k):
        calls.append(1)
        return orig(*a, **k)

    monkeypatch.setattr(sa, "sharded_fused_segment_agg", spy)
    return calls


@needs_mesh
def test_groupagg_routes_row_sharded_table(mesh, monkeypatch):
    from repro.relational import GroupAgg, Scan, Table, execute
    rng = np.random.default_rng(3)
    n = 640
    key = np.sort(rng.integers(0, 37, n)).astype(np.int32)
    val = rng.integers(-40, 40, n).astype(np.float32)
    t = Table.from_columns(k=key, v=val)
    plan = GroupAgg(Scan("L", ("k", "v")), ("k",),
                    (("s", "sum", "v"), ("c", "count", None),
                     ("mn", "min", "v"), ("mx", "max", "v"),
                     ("avg", "mean", "v")))
    want = execute(plan, {"L": t}).to_numpy()
    calls = _route_counter(monkeypatch)
    got = execute(plan, {"L": t.shard_rows(mesh, "data")}).to_numpy()
    assert calls, "row-sharded GroupAgg did not take the distributed path"
    assert set(want) == set(got)
    for k in want:
        assert np.array_equal(np.asarray(want[k], np.float32),
                              np.asarray(got[k], np.float32)), k


@needs_mesh
def test_grouped_aggcall_routes_row_sharded_table(mesh, monkeypatch):
    from repro.core import (Assign, Const, CursorLoop, If, Program, Var,
                            aggify, let)
    from repro.relational import Scan, Table, execute
    from repro.relational.plan import AggCall
    rng = np.random.default_rng(5)
    n = 640
    key = np.sort(rng.integers(0, 23, n)).astype(np.int32)
    cost = rng.integers(1, 50, n).astype(np.float32)
    schema = ("ps_partkey", "ps_suppkey", "ps_supplycost")
    prog = Program(
        "sumCount", params=(),
        pre=[let("tot", Const(0.0)), let("cnt", Const(0.0))],
        loop=CursorLoop(
            Scan("PARTSUPP", schema),
            fetch=[("c", "ps_supplycost")],
            body=[If(Var("c") > Const(20.0),
                     [Assign("tot", Var("tot") + Var("c"))]),
                  Assign("cnt", Var("cnt") + Const(1.0))]),
        post=[], returns=("tot", "cnt"))
    cat = {"PARTSUPP": Table.from_columns(
        ps_partkey=key, ps_suppkey=np.zeros(n, np.int32),
        ps_supplycost=cost)}
    rp = aggify(prog)
    call = AggCall(rp.agg_call.child, rp.agg_call.aggregate,
                   rp.agg_call.param_binding, rp.agg_call.ordered,
                   rp.agg_call.sort_keys, rp.agg_call.sort_desc,
                   group_keys=("ps_partkey",), mode="fused")
    env = {"tot": jnp.float32(0.0), "cnt": jnp.float32(0.0)}
    want = execute(call, cat, env).to_numpy()
    cat_sh = {"PARTSUPP": cat["PARTSUPP"].shard_rows(mesh, "data")}
    calls = _route_counter(monkeypatch)
    got = execute(call, cat_sh, env).to_numpy()
    assert calls, "row-sharded grouped AggCall did not take the " \
                  "distributed path"
    for k in want:
        assert np.array_equal(np.asarray(want[k]), np.asarray(got[k])), k


# --------------------------------------------------------------------------
# tier-1 coverage without the flag: spawn a flagged interpreter
# --------------------------------------------------------------------------


def test_sharded_path_in_subprocess_8way_mesh():
    """Runs the end-to-end sharded story (kernel bitwise parity + GroupAgg
    routing) in a subprocess with an 8-way host mesh, so plain tier-1 (one
    device, per tests/conftest.py) still exercises the distributed path."""
    code = """
import numpy as np, jax, jax.numpy as jnp
assert jax.device_count() == 8, jax.device_count()
from jax.sharding import Mesh
from repro.kernels.segment_agg import fused_segment_agg
from repro.launch.sharded_agg import sharded_fused_segment_agg
from repro.relational import GroupAgg, Scan, Table, execute

mesh = Mesh(np.asarray(jax.devices()).reshape(8), ("data",))
rng = np.random.default_rng(7)
n, nseg = 4096, 300
segs = np.sort(rng.integers(0, nseg, n)).astype(np.int32)
vals = rng.integers(-50, 50, (n, 2)).astype(np.float32)
valid = rng.random((n, 2)) < 0.8
single = fused_segment_agg(jnp.asarray(vals), jnp.asarray(segs),
                           jnp.asarray(valid), nseg, backend="jnp")
shd = sharded_fused_segment_agg(jnp.asarray(vals), jnp.asarray(segs),
                                jnp.asarray(valid), nseg, mesh=mesh,
                                axis="data", backend="jnp")
assert np.array_equal(np.asarray(single), np.asarray(shd))

key = np.sort(rng.integers(0, 37, 640)).astype(np.int32)
val = rng.integers(-40, 40, 640).astype(np.float32)
t = Table.from_columns(k=key, v=val)
plan = GroupAgg(Scan("L", ("k", "v")), ("k",),
                (("s", "sum", "v"), ("c", "count", None),
                 ("mn", "min", "v"), ("mx", "max", "v")))
want = execute(plan, {"L": t}).to_numpy()
import repro.launch.sharded_agg as sa
calls = []
orig = sa.sharded_fused_segment_agg
sa.sharded_fused_segment_agg = lambda *a, **k: (calls.append(1),
                                                orig(*a, **k))[1]
got = execute(plan, {"L": t.shard_rows(mesh, "data")}).to_numpy()
assert calls, "GroupAgg did not route through the sharded path"
for k in want:
    assert np.array_equal(np.asarray(want[k], np.float32),
                          np.asarray(got[k], np.float32)), k
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


def test_row_split_is_per_table_in_subprocess_4way_mesh():
    """One plan over a row-split fact table and a replicated dimension
    table, served (traced under ``jit``) and run directly: the grouped
    node whose rows are the fact table's launches per shard; the one
    whose rows are the dimension table's stays on one device, though the
    plan also scans the split table.  Both equal the one-device run."""
    code = """
import numpy as np, jax
assert jax.device_count() == 4, jax.device_count()
from jax.sharding import Mesh
from repro.relational import Table, execute
from repro.relational.engine import grouped_route
from repro.relational.plan import GroupAgg, Join, Scan
from repro.serve import AggServer
import repro.launch.sharded_agg as sa

mesh = Mesh(np.asarray(jax.devices()).reshape(4), ("data",))
rng = np.random.default_rng(3)
n, nd = 4096, 50
F = Table.from_columns(fk=rng.integers(0, nd, n).astype(np.int32),
                       id=np.arange(n, dtype=np.int32),
                       v=rng.integers(-9, 9, n).astype(np.float32))
D = Table.from_columns(dk=np.arange(nd, dtype=np.int32),
                       g=(np.arange(nd) % 8).astype(np.int32),
                       w=rng.integers(0, 9, nd).astype(np.float32))
fact_rows = GroupAgg(Join(Scan("F", ("fk", "id", "v")),
                          Scan("D", ("dk", "g", "w")), "fk", "dk"),
                     ("g",), (("s", "sum", "v"), ("mx", "max", "w")),
                     max_groups=8)
dim_rows = GroupAgg(Join(Scan("D", ("dk", "g", "w")),
                         Scan("F", ("fk", "id", "v")), "dk", "id"),
                    ("g",), (("s", "sum", "v"), ("mx", "max", "w")),
                    max_groups=8)
one = {"F": F, "D": D}
split = {"F": F.shard_rows(mesh, "data"), "D": D}
assert split["F"].row_split == (mesh, "data") and split["D"].row_split is None

calls = []
for name in ("sharded_fused_segment_agg", "sharded_sortfree_segment_agg"):
    orig = getattr(sa, name)
    setattr(sa, name, lambda *a, _o=orig, **k: (calls.append(1),
                                                _o(*a, **k))[1])

def same(a, b):
    a, b = a.to_numpy(), b.to_numpy()
    oa, ob = np.argsort(a["g"]), np.argsort(b["g"])
    return all(np.array_equal(a[c][oa], b[c][ob]) for c in a)

srv = AggServer(split)
for plan, per_shard in ((fact_rows, True), (dim_rows, False)):
    want = execute(plan, one)
    t = jax.eval_shape(lambda c: execute(plan.child, c), split)
    r = grouped_route(plan, t)
    assert (r.rows == n // 4) == per_shard, r
    for run in (lambda: execute(plan, split),
                lambda: jax.jit(lambda c: execute(plan, c))(split),
                lambda: srv.execute(plan)):
        calls.clear()
        got = run()
        assert bool(calls) == per_shard, (plan is fact_rows, calls)
        assert same(want, got)
srv.close()
print("OK")
"""
    src = os.path.join(os.path.dirname(__file__), "..", "src")
    env = {**os.environ,
           "JAX_PLATFORMS": "cpu",
           "XLA_FLAGS": (os.environ.get("XLA_FLAGS", "") +
                         " --xla_force_host_platform_device_count=4"),
           "PYTHONPATH": os.path.abspath(src) + os.pathsep +
                         os.environ.get("PYTHONPATH", "")}
    r = subprocess.run([sys.executable, "-c", code], capture_output=True,
                       text=True, env=env)
    assert r.returncode == 0 and "OK" in r.stdout, r.stderr
