"""Ingest parity battery for resident incremental aggregation.

The contract under test (docs/serving.md "Incremental ingest"):

* N micro-batches folded into the resident (C, R, S) moment state ==
  one one-shot recompute over the final table — across every fused op
  (sum/count/min/max/mean/argmin/argmax), key dtypes, new-key arrival,
  overflow growth, and invalid rows in the batch payload;
* ``append_rows`` preserves compiled executables (no retrace while rows
  fit the spare capacity) and EXTENDS the slot table incrementally
  (``ServeStats.slot_extends`` moves, ``slot_build_count`` does not),
  while ``update_table`` still invalidates both;
* an append-shaped ``update_table`` draws a ``DeprecationWarning``
  pointing at the append verbs;
* a fold failure (the ``ingest_fold`` chaos site) degrades to the jnp
  fold under the guard and NEVER corrupts the resident state;
* ``fold_moments`` is the ``shard_merge`` collective algebra applied
  host-side (pinned against ``moment_merge_aggregate``);
* the sharded fold variant (8-way host mesh, subprocess) folds a
  micro-batch into sharded resident moments with the same results;
* ``REPRO_INCR_AGG=off`` reduces ingest to append (and stays correct).
"""
import os
import subprocess
import sys

import numpy as np
import pytest

import jax.numpy as jnp

from repro.core.aggregate import fold_moments
from repro.launch.sharded_agg import moment_merge_aggregate
from repro.relational import Table, execute
from repro.relational import keyslot
from repro.relational.plan import GroupAgg, Scan
from repro.reliability import faults
from repro.serve import AggServer, BoundOverflow, ServeRequest

SCHEMA = ("k", "v", "p")


def _plan(max_groups=128, keys=("k",)):
    return GroupAgg(Scan("T", SCHEMA), keys,
                    (("s", "sum", "v"), ("c", "count", None),
                     ("mn", "min", "v"), ("mx", "max", "v"),
                     ("me", "mean", "v"),
                     ("am", "argmin", ("v", "p")),
                     ("ax", "argmax", ("v", "p"))),
                    max_groups=max_groups)


def _mk_table(n=512, card=40, seed=0, spare=0, kdtype=np.int32):
    # integer-valued f32 payloads: every moment is f32-exact, so the
    # resident fold and the one-shot recompute agree BITWISE and the
    # parity dicts compare with == (no tolerance hiding a real bug)
    rng = np.random.default_rng(seed)
    cap = n + spare
    cols = {"k": rng.integers(0, card, cap).astype(kdtype),
            "v": rng.integers(-40, 40, cap).astype(np.float32),
            "p": rng.integers(0, 10_000, cap).astype(np.int32)}
    valid = np.arange(cap) < n
    return Table({c: jnp.asarray(a) for c, a in cols.items()},
                 jnp.asarray(valid))


def _batch(nb, card, seed, kdtype=np.int32):
    rng = np.random.default_rng(seed)
    return {"k": rng.integers(0, card, nb).astype(kdtype),
            "v": rng.integers(-40, 40, nb).astype(np.float32),
            "p": rng.integers(0, 10_000, nb).astype(np.int32)}


def _groups(t: Table) -> dict:
    out = t.to_numpy()
    keycols = [c for c in ("k", "k2") if c in out]
    return {tuple(int(out[c][i]) for c in keycols):
            tuple(float(out[c][i]) for c in ("s", "c", "mn", "mx", "me",
                                             "am", "ax"))
            for i in range(len(out["s"]))}


def _reference(srv: AggServer, plan) -> dict:
    return _groups(execute(plan, {"T": srv.table("T")}))


def test_fold_moments_is_the_shard_merge_algebra():
    # host-side fold == moment_merge_aggregate().merge, element for element
    rng = np.random.default_rng(3)
    C, S = 3, 17

    def rand():
        return jnp.stack(
            [jnp.asarray(rng.normal(size=(C, S)).astype(np.float32)),
             jnp.asarray(rng.integers(0, 5, (C, S)).astype(np.float32)),
             jnp.asarray(rng.normal(size=(C, S)).astype(np.float32)),
             jnp.asarray(rng.normal(size=(C, S)).astype(np.float32))],
            axis=1)

    a, b = rand(), rand()
    want = moment_merge_aggregate(C, S).merge(a, b)
    got = fold_moments(a, b)
    assert np.array_equal(np.asarray(want), np.asarray(got))
    # commutative, and identity-absorbing on the identity tensor
    assert np.array_equal(np.asarray(fold_moments(b, a)), np.asarray(got))
    ident = moment_merge_aggregate(C, S).identity()
    assert np.array_equal(np.asarray(fold_moments(a, ident)),
                          np.asarray(a))


def test_fold_moments_index_rows_merge_lexicographically():
    # R=6: the argmin row follows the min KEY row; on a key tie the
    # smaller global row index wins (first-attaining order)
    moments = (("min", "argmin_first"),)
    fills = np.asarray([0.0, 0.0, np.inf, -np.inf, np.inf, np.inf],
                       np.float32).reshape(1, 6, 1)
    a = np.tile(fills, (1, 1, 3)).astype(np.float32)
    b = a.copy()
    # slot 0: a holds key 2 at row 10, b holds key 1 at row 50 → b wins
    a[0, 2, 0], a[0, 4, 0] = 2.0, 10.0
    b[0, 2, 0], b[0, 4, 0] = 1.0, 50.0
    # slot 1: key tie at 5 — rows 30 vs 7 → row 7 wins
    a[0, 2, 1], a[0, 4, 1] = 5.0, 30.0
    b[0, 2, 1], b[0, 4, 1] = 5.0, 7.0
    # slot 2: only a has data
    a[0, 2, 2], a[0, 4, 2] = 9.0, 3.0
    m = np.asarray(fold_moments(jnp.asarray(a), jnp.asarray(b),
                                moments=moments))
    assert m[0, 2, 0] == 1.0 and m[0, 4, 0] == 50.0
    assert m[0, 2, 1] == 5.0 and m[0, 4, 1] == 7.0
    assert m[0, 2, 2] == 9.0 and m[0, 4, 2] == 3.0


@pytest.mark.parametrize("kdtype", [np.int32, np.int16, np.float32])
def test_micro_batches_fold_to_one_shot_parity(kdtype):
    # the headline contract: N folded micro-batches == one recompute
    # over the final table, for every fused op at once — including ties
    # (payload values collide freely) and NEW keys arriving mid-stream
    t = _mk_table(n=512, card=40, seed=0, spare=512, kdtype=kdtype)
    srv = AggServer({"T": t})
    plan = _plan()
    assert _groups(srv.snapshot(plan)) == _reference(srv, plan)  # seed
    for i in range(5):
        srv.ingest("T", _batch(48, 60, seed=10 + i, kdtype=kdtype))
        assert _groups(srv.snapshot(plan)) == _reference(srv, plan), i
    assert srv.stats.folds == 5 and srv.stats.ingests == 5
    # the folds were O(batch): one slot build at seed, extends after
    assert srv.stats.slot_builds <= 2   # server build + resident seed share
    srv.close()


def test_two_key_columns_fold_parity():
    t = _mk_table(n=512, card=6, seed=1, spare=256)
    t = t.with_column("k2", jnp.asarray(
        np.random.default_rng(2).integers(0, 4, t.capacity)
        .astype(np.int16)))
    srv = AggServer({"T": t})
    plan = GroupAgg(Scan("T", ("k", "k2", "v", "p")), ("k", "k2"),
                    (("s", "sum", "v"), ("c", "count", None),
                     ("mn", "min", "v"), ("mx", "max", "v"),
                     ("me", "mean", "v"),
                     ("am", "argmin", ("v", "p")),
                     ("ax", "argmax", ("v", "p"))), max_groups=64)
    assert _groups(srv.snapshot(plan)) == _reference(srv, plan)
    rng = np.random.default_rng(7)
    for i in range(3):
        nb = 32
        srv.ingest("T", {"k": rng.integers(0, 6, nb).astype(np.int32),
                         "k2": rng.integers(0, 4, nb).astype(np.int16),
                         "v": rng.integers(-9, 9, nb).astype(np.float32),
                         "p": rng.integers(0, 99, nb).astype(np.int32)})
        assert _groups(srv.snapshot(plan)) == _reference(srv, plan), i
    srv.close()


def test_batch_with_invalid_rows_is_filtered():
    t = _mk_table(n=400, card=30, seed=4, spare=300)
    srv = AggServer({"T": t})
    plan = _plan()
    srv.snapshot(plan)
    b = _batch(64, 50, seed=40)
    bt = Table({c: jnp.asarray(a) for c, a in b.items()},
               jnp.asarray(np.arange(64) % 3 != 0))   # 1/3 invalid
    srv.ingest("T", bt)
    assert _groups(srv.snapshot(plan)) == _reference(srv, plan)
    srv.close()


def test_inferred_bound_grows_through_overflowing_folds():
    # no declared bound: the server infers one from the sketch; batches
    # then introduce enough distinct keys to overflow the resident
    # bucket, and the double-and-retry (ResidentAgg.grow) absorbs them
    t = _mk_table(n=1024, card=20, seed=5, spare=1024)
    srv = AggServer({"T": t})
    plan = _plan(max_groups=None)
    srv.snapshot(plan)
    bound0 = srv.describe(plan)["bound"]
    assert bound0 is not None
    rng = np.random.default_rng(6)
    for i in range(4):
        nb = 128
        srv.ingest("T", {"k": rng.integers(0, 400, nb).astype(np.int32),
                         "v": rng.integers(-5, 5, nb).astype(np.float32),
                         "p": rng.integers(0, 99, nb).astype(np.int32)})
        assert _groups(srv.snapshot(plan)) == _reference(srv, plan), i
    srv.close()


def test_declared_bound_overflow_surfaces_typed_error_and_append_lands():
    t = _mk_table(n=1024, card=20, seed=8, spare=1024)
    srv = AggServer({"T": t}, guard=True)
    plan = _plan(max_groups=200)          # bucket 256, not growable
    srv.snapshot(plan)
    rng = np.random.default_rng(9)
    nb = 512
    big = {"k": rng.integers(0, 3000, nb).astype(np.int32),
           "v": np.ones(nb, np.float32),
           "p": np.zeros(nb, np.int32)}
    v0 = srv.table("T").version
    with pytest.raises(BoundOverflow):
        srv.ingest("T", big)
    assert srv.table("T").version != v0   # the append itself landed
    # residency dropped; snapshot falls back to a recompute — and the
    # recompute itself now exceeds the declared bound, so nothing is
    # silently wrong: the plan's own overflow contract takes over
    with pytest.raises(Exception):
        srv.snapshot(plan)
    srv.close()


def test_chaos_ingest_fold_degrades_without_corrupting_state():
    t = _mk_table(n=512, card=40, seed=11, spare=512)
    srv = AggServer({"T": t}, guard=True)
    plan = _plan()
    srv.snapshot(plan)
    with faults.inject("ingest_fold:1"):
        srv.ingest("T", _batch(48, 60, seed=50))
    # the primary fold was killed; the guard retried on the jnp path
    assert srv.guard_stats.backend_failures >= 1
    assert srv.guard_stats.degraded_launches >= 1
    assert _groups(srv.snapshot(plan)) == _reference(srv, plan)
    # and the resident state kept folding afterwards (not corrupted)
    srv.ingest("T", _batch(48, 60, seed=51))
    assert _groups(srv.snapshot(plan)) == _reference(srv, plan)
    srv.close()


def test_snapshot_catches_up_on_plain_appends():
    # append_rows does NOT fold eagerly; the next snapshot walks the
    # version chain and folds the pending positions in one catch-up
    t = _mk_table(n=512, card=40, seed=12, spare=512)
    srv = AggServer({"T": t})
    plan = _plan()
    srv.snapshot(plan)
    folds0 = srv.stats.folds
    srv.append_rows("T", _batch(32, 50, seed=60))
    srv.append_rows("T", _batch(32, 50, seed=61))
    assert srv.stats.folds == folds0          # nothing folded yet
    assert _groups(srv.snapshot(plan)) == _reference(srv, plan)
    assert srv.stats.folds == folds0 + 1      # one catch-up fold
    srv.close()


def test_append_rows_preserves_executables_and_extends_slots():
    # the acceptance criterion: appends that fit the spare capacity keep
    # the compiled executable (trace counter unchanged) and EXTEND the
    # slot table (extend counter moves, build counter does not) — while
    # update_table still invalidates both
    t = _mk_table(n=512, card=40, seed=13, spare=512)
    srv = AggServer({"T": t})
    plan = _plan()
    srv.execute(plan)
    traces = srv.stats.traces
    builds_srv = srv.stats.slot_builds
    builds_key = keyslot.slot_build_count()

    srv.append_rows("T", _batch(64, 60, seed=70))
    got = _groups(srv.execute(plan))
    assert srv.stats.traces == traces                 # executable survived
    assert srv.stats.slot_builds == builds_srv        # no rebuild …
    assert keyslot.slot_build_count() == builds_key   # … keyslot spy agrees
    assert srv.stats.slot_extends >= 1                # extended instead
    assert got == _reference(srv, plan)   # (the reference recompute does
    #                                       its own build — check after)

    # REPLACE: both caches go
    t2 = srv.table("T").with_column(
        "v", jnp.asarray(np.asarray(srv.table("T").columns["v"]) * 2))
    srv.update_table("T", t2)
    assert _groups(srv.execute(plan)) == _reference(srv, plan)
    assert srv.stats.traces == traces + 1             # retraced
    assert srv.stats.slot_builds == builds_srv + 1    # rebuilt
    srv.close()


def test_append_shaped_update_table_draws_deprecation_warning():
    t = _mk_table(n=256, card=20, seed=14, spare=64)
    srv = AggServer({"T": t})
    b = _batch(16, 30, seed=80)
    mask = np.asarray(t.mask()).copy()
    pos = np.flatnonzero(~mask)[:16]
    cols = {c: np.asarray(a).copy() for c, a in t.columns.items()}
    for c in cols:
        cols[c][pos] = b[c]
    mask[pos] = True
    t2 = Table({c: jnp.asarray(a) for c, a in cols.items()},
               jnp.asarray(mask))
    with pytest.warns(DeprecationWarning, match="append_rows"):
        srv.update_table("T", t2)
    # a genuine replace stays silent
    t3 = t.with_column("v", jnp.asarray(
        np.asarray(t.columns["v"]) * np.float32(3.0)))
    import warnings as _w
    with _w.catch_warnings():
        _w.simplefilter("error", DeprecationWarning)
        srv.update_table("T", t3)
    srv.close()


def test_kill_switch_reduces_ingest_to_append(monkeypatch):
    monkeypatch.setenv("REPRO_INCR_AGG", "off")
    t = _mk_table(n=256, card=20, seed=15, spare=256)
    srv = AggServer({"T": t})
    plan = _plan()
    srv.snapshot(plan)                        # plain compute, no residency
    srv.ingest("T", _batch(32, 30, seed=90))  # == append_rows
    assert srv.stats.folds == 0
    assert srv.stats.appends == 1
    assert _groups(srv.snapshot(plan)) == _reference(srv, plan)
    srv.close()


def test_serve_request_snapshot_consistency():
    t = _mk_table(n=512, card=40, seed=16, spare=256)
    srv = AggServer({"T": t})
    plan = _plan()
    res = srv.serve(ServeRequest(plan=plan, consistency="snapshot"))
    assert _groups(res.table) == _reference(srv, plan)
    assert res.version == srv.table("T").version
    v2 = srv.ingest("T", _batch(32, 50, seed=95))
    res2 = srv.serve_async(
        ServeRequest(plan=plan, consistency="snapshot")).result(timeout=30)
    assert res2.version == v2
    assert _groups(res2.table) == _reference(srv, plan)
    with pytest.raises(ValueError):
        srv.serve(ServeRequest(plan=plan, consistency="bogus"))
    srv.close()


def test_sharded_fold_in_subprocess_8way_mesh():
    """Folds a replicated micro-batch into SHARDED resident moments on an
    8-way host mesh (subprocess — tier-1 runs single-device), asserting
    the fold routed through ``sharded_fold_batch`` and that the snapshot
    matches the one-shot recompute."""
    code = """
import numpy as np, jax, jax.numpy as jnp
assert jax.device_count() == 8, jax.device_count()
from jax.sharding import Mesh
from repro.relational import Table, execute
from repro.relational.plan import GroupAgg, Scan
from repro.serve.incremental import ResidentAgg
import repro.launch.sharded_agg as sa

mesh = Mesh(np.asarray(jax.devices()).reshape(8), ("data",))
rng = np.random.default_rng(5)
cap, n0, nb = 1024, 768, 128
cols = {"k": rng.integers(0, 100, cap).astype(np.int32),
        "v": rng.integers(-40, 40, cap).astype(np.float32),
        "p": rng.integers(0, 10000, cap).astype(np.int32)}
t = Table({c: jnp.asarray(a) for c, a in cols.items()},
          jnp.asarray(np.arange(cap) < n0))
plan = GroupAgg(Scan("T", ("k", "v", "p")), ("k",),
                (("s", "sum", "v"), ("c", "count", None),
                 ("mn", "min", "v"), ("am", "argmin", ("v", "p")),
                 ("ax", "argmax", ("v", "p"))), max_groups=128)
res = ResidentAgg.admit(plan, "T", ("k",), t, 128)
assert res is not None
res.seed(t)
# the appended rows were pre-staged at positions n0..n0+nb; marking
# them valid and sharding the table models an ingested micro-batch
# over a row-sharded resident
t2 = Table(dict(t.columns), jnp.asarray(np.arange(cap) < n0 + nb))
t2s = t2.shard_rows(mesh, "data")
calls = []
orig = sa.sharded_fold_batch
sa.sharded_fold_batch = lambda *a, **k: (calls.append(1), orig(*a, **k))[1]
res.fold(t2s, np.arange(n0, n0 + nb))
assert calls, "fold did not take the sharded path"
got = res.snapshot(t2s).to_numpy()
want = execute(plan, {"T": t2}).to_numpy()
gd = {int(got["k"][i]): tuple(float(got[c][i])
      for c in ("s", "c", "mn", "am", "ax")) for i in range(len(got["k"]))}
wd = {int(want["k"][i]): tuple(float(want[c][i])
      for c in ("s", "c", "mn", "am", "ax")) for i in range(len(want["k"]))}
assert gd == wd, (sorted(gd.items())[:4], sorted(wd.items())[:4])
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
