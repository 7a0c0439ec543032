"""Cache-invalidation battery for the aggregate-serving layer.

The contract under test (docs/serving.md):

* the slot table is built exactly ONCE per (table version, key set,
  bucket) — repeated parameterized calls amortize slotting to zero;
* ``update_table`` is the REPLACE verb: it rebuilds the slot table
  exactly once, FROM THE NEW VERSION (spied on
  ``relational/keyslot.py``), and invalidates the executables of every
  plan scanning the table — content may have changed arbitrarily, so
  nothing derived from the old version survives.  A stale slot read is
  structurally impossible because slot arrays are executable *arguments*
  keyed by ``Table.version``;
* ``append_rows`` is the APPEND verb: executables SURVIVE (no retrace
  while rows fit the spare capacity) and the slot table EXTENDS
  incrementally instead of rebuilding (tests/test_incremental_ingest.py
  holds the full append/ingest battery);
* a user-declared bound that overflows raises eagerly at the slot build;
  an inferred bound grows and revalidates instead;
* ``REPRO_AGG_SERVE=off`` kills every cache but stays correct."""
import numpy as np
import pytest

import jax.numpy as jnp

from repro.relational import Table, concat, execute
from repro.relational import keyslot
from repro.relational.plan import GroupAgg, Scan
from repro.serve import AggServer

N = 160
SCHEMA = ("k", "v")


def _table(n=N, card=12, seed=0):
    # explicit all-true mask: a later ``filter`` then mutates the mask
    # VALUES without changing the pytree structure (None → array would
    # be a structural change, which legitimately retraces)
    rng = np.random.default_rng(seed)
    return Table({"k": jnp.asarray(rng.integers(0, card, n).astype(np.int32)),
                  "v": jnp.asarray(rng.integers(-4, 5, n).astype(np.float32))},
                 jnp.ones(n, bool))


def _plan(max_groups=24):
    return GroupAgg(Scan("T", SCHEMA), ("k",),
                    (("s", "sum", "v"), ("c", "count", None),
                     ("mx", "max", "v")), max_groups=max_groups)


def _groups(t: Table) -> dict:
    out = t.to_numpy()
    return {int(k): (s, c, m) for k, s, c, m in
            zip(out["k"], out["s"], out["c"], out["mx"])}


def test_slot_table_built_exactly_once_across_repeats():
    t = _table()
    srv = AggServer({"T": t})
    plan = _plan()
    before = keyslot.slot_build_count()
    ref = _groups(srv.execute(plan))
    for _ in range(4):
        assert _groups(srv.execute(plan)) == ref
    assert srv.stats.slot_builds == 1
    assert srv.stats.slot_hits == 4
    # the keyslot-level spy agrees: one probe-loop build total — the
    # executable's in-trace call was intercepted by provide_slots
    assert keyslot.slot_build_count() - before == 1
    assert srv.stats.traces == 1


def test_compiled_text_reads_the_served_executable():
    """``compiled_text`` lowers the executable a request runs with that
    request's own arguments: no retrace, no new executable, no slot
    rebuild — and the text is the optimized program the device runs."""
    srv = AggServer({"T": _table()})
    plan = _plan()
    srv.execute(plan)
    txt = srv.compiled_text(plan)
    assert "HloModule" in txt
    assert srv.stats.traces == 1 and srv.stats.slot_builds == 1
    assert srv.describe(plan)["executables"] == 1


@pytest.mark.parametrize("col,sortfree", [("iv", True), ("v", False)])
def test_slot_provisioning_follows_the_engine_route(monkeypatch, col,
                                                    sortfree):
    """High-cardinality grouping under a kernel backend: an op the kernel
    cannot take (an int column) keeps the engine on the sort-free route,
    so the server caches its slot table; a kernel op sends the engine
    down the sorted route (the kernel-grid rule), so the server builds
    none.  Either way the served result equals the sorted one."""
    from repro.relational.engine import grouped_route
    monkeypatch.setenv("REPRO_GROUPAGG_FUSED", "interpret")
    n, card = 20000, 10000
    rng = np.random.default_rng(5)
    t = Table({"k": jnp.asarray(rng.integers(0, card, n).astype(np.int32)),
               "v": jnp.asarray(rng.integers(-4, 5, n).astype(np.float32)),
               "iv": jnp.asarray(rng.integers(-4, 5, n).astype(np.int32))},
              jnp.ones(n, bool))
    plan = GroupAgg(Scan("T", ("k", "v", "iv")), ("k",),
                    (("s", "sum", col),), max_groups=card)
    assert grouped_route(plan, t, cached=True).sortfree == sortfree
    srv = AggServer({"T": t})
    before = keyslot.slot_build_count()
    got = srv.execute(plan)
    srv.execute(plan)
    assert (srv.describe(plan)["slot_scan"] == "T") == sortfree
    assert srv.stats.slot_builds == keyslot.slot_build_count() - before \
        == int(sortfree)
    monkeypatch.setenv("REPRO_GROUPAGG_SORTFREE", "off")
    want = execute(plan, {"T": t}).to_numpy()
    got = got.to_numpy()
    og, ow = np.argsort(got["k"]), np.argsort(want["k"])
    for c in want:
        np.testing.assert_array_equal(got[c][og], want[c][ow], err_msg=c)


def test_mutation_rebuilds_slots_once_from_new_version(monkeypatch):
    t = _table()
    srv = AggServer({"T": t})
    plan = _plan()

    eager_builds = []   # versions of CONCRETE (eager) probe builds
    orig = keyslot.slot_state_build

    def spy(table, keys, bucket, expand=None):
        import jax as _jax
        if not isinstance(next(iter(table.columns.values())),
                          _jax.core.Tracer):
            eager_builds.append(table.version)
        return orig(table, keys, bucket, expand)

    monkeypatch.setattr(keyslot, "slot_state_build", spy)

    srv.execute(plan)
    srv.execute(plan)
    assert eager_builds == [t.version]

    # REPLACE: content changed arbitrarily (filter mutates the mask), so
    # the slot table rebuilds once from the NEW version and the plan's
    # executables are invalidated (the replace contract)
    t2 = t.filter(jnp.asarray(np.asarray(t.columns["v"]) >= 0))
    srv.update_table("T", t2)
    got = _groups(srv.execute(plan))
    srv.execute(plan)

    assert eager_builds == [t.version, t2.version]   # rebuilt once, new version
    assert srv.stats.slot_builds == 2
    # stale-read impossible: rebuilt slots + fresh executable == fresh
    assert got == _groups(execute(plan, {"T": t2}))
    assert got != _groups(execute(plan, {"T": t}))


def test_update_table_invalidates_executables():
    # the REPLACE verb drops every executable of every plan scanning the
    # table — even for a shape-compatible swap the old trace may have
    # folded stale content decisions in, so nothing derived from the old
    # version survives (append_rows is the verb that keeps them; see
    # tests/test_incremental_ingest.py)
    t = _table()
    srv = AggServer({"T": t})
    plan = _plan()
    srv.execute(plan)
    traces = srv.stats.traces
    t2 = t.with_column("v", jnp.asarray(
        np.asarray(t.columns["v"]) * np.float32(2.0)))
    srv.update_table("T", t2)
    got = _groups(srv.execute(plan))
    assert srv.stats.traces == traces + 1            # replace: retrace
    assert srv.stats.slot_builds == 2                # new version: one rebuild
    assert got == _groups(execute(plan, {"T": t2}))


def test_append_mutation_retraces_and_stays_correct():
    t = _table()
    srv = AggServer({"T": t})
    plan = _plan()
    srv.execute(plan)
    traces = srv.stats.traces
    extra = _table(n=32, card=12, seed=9)
    t2 = concat(t, extra)                            # capacity grows
    srv.update_table("T", t2)
    got = _groups(srv.execute(plan))
    assert srv.stats.traces == traces + 1            # new shape bucket
    assert srv.stats.slot_builds == 2
    assert got == _groups(execute(plan, {"T": t2}))


def test_declared_overflow_raises_eagerly():
    rng = np.random.default_rng(3)
    n = 400
    t = Table.from_columns(k=rng.permutation(n).astype(np.int32),
                           v=np.ones(n, np.float32))
    srv = AggServer({"T": t})
    # ~400 distinct keys vs a 128-slot bucket: the server's eager slot
    # build must raise (the engine contract), not poison inside a trace
    with pytest.raises(ValueError, match="beyond the declared dense bound"):
        srv.execute(_plan(max_groups=16))


def test_inferred_bound_grows_on_mutation():
    rng = np.random.default_rng(4)
    t = Table.from_columns(
        k=rng.integers(0, 60, 400).astype(np.int32),
        v=rng.integers(-4, 5, 400).astype(np.float32))
    srv = AggServer({"T": t})
    plan = _plan(max_groups=None)                    # server sketches a bound
    srv.execute(plan)
    d = srv.describe(plan)
    assert d["inferred"] and d["bound"] == 128
    # the mutated table carries ~340 distinct keys — past the inferred
    # bucket: the build overflow doubles the bound until it validates
    extra = Table.from_columns(
        k=(1000 + rng.permutation(300)).astype(np.int32),
        v=np.ones(300, np.float32))
    t2 = concat(t, extra)
    srv.update_table("T", t2)
    got = _groups(srv.execute(plan))
    assert srv.describe(plan)["bound"] == 512
    assert got == _groups(execute(plan, {"T": t2}))


def test_kill_switch_disables_caches(monkeypatch):
    monkeypatch.setenv("REPRO_AGG_SERVE", "off")
    t = _table()
    srv = AggServer({"T": t})
    plan = _plan()
    ref = _groups(execute(plan, {"T": t}))
    assert _groups(srv.execute(plan)) == ref
    assert _groups(srv.submit(plan).result(timeout=60)) == ref
    assert srv.stats.requests == 0 and srv.stats.traces == 0
    assert srv.stats.slot_builds == 0
