"""Chaos battery for the serving guard (serve/guard.py + reliability/).

Every injected failure must surface as the matching typed ``ServeError``
— or be absorbed by the recovery ladder and produce a **bit-correct**
result against the numpy oracle of tests/serving_cases.py.  Faults are
deterministic (named sites, shot counts, no randomness), so each test
replays exactly; the ``inject`` table is process-global, which is fine
under pytest's sequential runner.
"""
from __future__ import annotations

import logging
import os
import subprocess
import sys
import threading
import time

import numpy as np
import pytest

from repro.reliability import faults
from repro.reliability.faults import FaultInjected
from repro.serve import (AggServer, BackendFailure, BoundOverflow,
                         DeadlineExceeded, PoisonedResult, QueueFull,
                         ServeError, ServerClosed, SlotTableStale)

from serving_cases import assert_same_groups, build_case, oracle, result_groups

# a hung drain or deadlocked dispatcher must fail, not stall the suite
# (enforced in CI where pytest-timeout is installed; a registered no-op
# marker locally)
pytestmark = pytest.mark.timeout(300)

# ~6 distinct keys under a declared bound — the everyday shape
CASE_SMALL = {"seed": 1, "n": 160, "key_dtypes": ("int32",), "card": 6,
              "aggs": ("sum", "count", "min", "max"), "max_groups": 24}

# ~400 distinct keys, bound INFERRED from the sketch — the shape where
# an undershooting sketch actually overflows its first bucket
CASE_WIDE = {"seed": 11, "n": 1600, "key_dtypes": ("int32",), "card": 400,
             "aggs": ("sum", "count")}

# parameterized filter child: multiple request signatures + vmapped lanes
CASE_FILTERED = {"seed": 7, "n": 168, "key_dtypes": ("int32",), "card": 5,
                 "filtered": True, "params": (-1.0, 0.0, 1.0, 2.0),
                 "aggs": ("sum", "count", "max"), "max_groups": 16}


def _fresh(case, **kw):
    t, plan, keys, aggs, envs = build_case(case)
    kw.setdefault("max_batch", 8)
    kw.setdefault("batch_window_s", 0.0)
    srv = AggServer({"T": t}, **kw)
    return srv, t, plan, keys, aggs, envs


def _check(srv_result, t, keys, aggs, env, label):
    assert_same_groups(result_groups(srv_result, keys, aggs),
                       oracle(t, keys, aggs, env), label)


# ---------------------------------------------------------------------------
# registry mechanics + env hook liveness
# ---------------------------------------------------------------------------


def test_unknown_site_rejected():
    with pytest.raises(ValueError, match="unknown fault site"):
        faults.configure("not_a_site")


def test_shot_counts_consume_exactly():
    with faults.inject(""):                     # pin a disarmed baseline
        with faults.inject("selftest:2"):       # (CI arms selftest via env)
            assert faults.fire("selftest")
            assert faults.fire("selftest")
            assert not faults.fire("selftest")
        assert not faults.fire("selftest")      # restored (disarmed)


def test_env_hook_is_live():
    """REPRO_FAULTS arms the table at import — the CI chaos step runs the
    suite under REPRO_FAULTS=selftest and this test proves the hook came
    live end-to-end; without the env it proves the same in a
    subprocess."""
    spec = os.environ.get("REPRO_FAULTS")
    if spec:
        assert faults.active_spec() == spec
        if "selftest" in spec:
            assert faults.fired("selftest") or faults.fire("selftest")
        return
    out = subprocess.run(
        [sys.executable, "-c",
         "from repro.reliability import faults; "
         "assert faults.active_spec() == 'selftest'; "
         "assert faults.fire('selftest'); print('LIVE')"],
        env={**os.environ, "REPRO_FAULTS": "selftest",
             "PYTHONPATH": "src"},
        cwd=os.path.dirname(os.path.dirname(os.path.abspath(__file__))),
        capture_output=True, text=True, timeout=120)
    assert out.returncode == 0, out.stderr
    assert "LIVE" in out.stdout


# ---------------------------------------------------------------------------
# structured errors: declared bound overflow, typed on the future
# ---------------------------------------------------------------------------


def test_declared_overflow_is_typed_boundoverflow():
    case = dict(CASE_WIDE, max_groups=2)    # bucket 128 << ~400 groups
    srv, t, plan, keys, aggs, envs = _fresh(case)
    with srv:
        with pytest.raises(BoundOverflow,
                           match="beyond the declared dense bound"):
            srv.execute(plan, {})
        fut = srv.submit(plan, {})
        err = fut.exception(timeout=120)
        assert isinstance(err, BoundOverflow)
        assert isinstance(err, ValueError)      # legacy contract holds
        assert isinstance(err, ServeError)


# ---------------------------------------------------------------------------
# poison detection + bounded bound recovery
# ---------------------------------------------------------------------------


def test_sketch_undershoot_grows_inferred_bound():
    """An undershooting sketch infers a too-small bound; the eager slot
    build catches the overflow and double-and-rebuilds until it fits —
    the request never fails and the result is bit-correct."""
    srv, t, plan, keys, aggs, envs = _fresh(CASE_WIDE)
    with srv, faults.inject("sketch_undershoot"):
        out = srv.execute(plan, {})
    _check(out, t, keys, aggs, {}, "undershoot-grown vs oracle")
    d = srv.describe(plan)
    assert d["inferred"]
    assert d["bound"] is not None and d["bound"] >= 400


def test_bound_unvalidated_poison_detected_and_retried():
    """The full ladder: the sketch undershoots AND the eager validation
    is skipped once, so a poisoned launch actually reaches the detector
    — which converts it to a doubled-bound retry, not NaNs."""
    srv, t, plan, keys, aggs, envs = _fresh(CASE_WIDE)
    with srv, faults.inject("sketch_undershoot:1,bound_unvalidated:1"):
        out = srv.execute(plan, {})
    _check(out, t, keys, aggs, {}, "poison-retried vs oracle")
    assert srv.guard_stats.poisoned >= 1
    assert srv.guard_stats.poison_retries >= 1


def test_poisoned_declared_bound_is_typed_not_silent():
    """A poisoned launch whose bound was user-declared cannot be grown —
    it must surface as PoisonedResult, never as NaNs in the caller's
    hands."""
    case = dict(CASE_WIDE, max_groups=2)
    srv, t, plan, keys, aggs, envs = _fresh(case)
    with srv, faults.inject("bound_unvalidated:1"):
        with pytest.raises(PoisonedResult, match="poison stamp"):
            srv.execute(plan, {})
    assert srv.guard_stats.poisoned == 1
    assert srv.guard_stats.poison_retries == 0


# ---------------------------------------------------------------------------
# slot-table staleness
# ---------------------------------------------------------------------------


def test_slot_stale_detected_and_rebuilt():
    srv, t, plan, keys, aggs, envs = _fresh(CASE_SMALL)
    with srv:
        with faults.inject("slot_stale:1"):
            _check(srv.execute(plan, {}), t, keys, aggs, {},
                   "stale-build launch vs oracle")
        # the corrupt tag is detected on the next hit; one rebuild heals
        _check(srv.execute(plan, {}), t, keys, aggs, {},
               "post-stale launch vs oracle")
        assert srv.guard_stats.stale_rebuilds == 1
        _check(srv.execute(plan, {}), t, keys, aggs, {}, "healed")
        assert srv.guard_stats.stale_rebuilds == 1     # healed for good


def test_slot_stale_unbounded_surfaces_typed():
    srv, t, plan, keys, aggs, envs = _fresh(CASE_SMALL)
    with srv, faults.inject("slot_stale"):
        srv.execute(plan, {})                   # build (tag corrupted)
        with pytest.raises(SlotTableStale):
            srv.execute(plan, {})               # rebuilds re-corrupt: bounded
    assert srv.guard_stats.stale_rebuilds >= 2


# ---------------------------------------------------------------------------
# backend failure → degradation ladder → recovery
# ---------------------------------------------------------------------------


def test_backend_failure_degrades_trips_and_recovers(caplog):
    clk = [0.0]
    srv, t, plan, keys, aggs, envs = _fresh(
        CASE_SMALL, breaker_threshold=2, breaker_cooldown_s=10.0,
        breaker_clock=lambda: clk[0])
    with srv:
        with faults.inject("backend_exc"), caplog.at_level(
                logging.WARNING, logger="repro.serve.agg_server"):
            # every primary launch raises; the ladder serves each request
            # on the degraded jnp executable — callers see only results
            for i in range(3):
                _check(srv.execute(plan, {}), t, keys, aggs, {},
                       f"degraded launch {i} vs oracle")
        gs = srv.guard_stats
        assert gs.degraded_launches == 3
        # threshold 2: two recorded failures trip the breaker; launch 3
        # goes straight to the degraded path without touching the primary
        assert gs.backend_failures == 2
        assert gs.breaker_trips == 1
        # every absorbed failure is counted; its exception is logged once
        # per (plan, signature)
        logged = [r for r in caplog.records
                  if "degraded jnp path" in r.getMessage()]
        assert len(logged) == 1
        assert isinstance(logged[0].exc_info[1], FaultInjected)
        assert srv.describe(plan)["breakers"][()] == "open"
        # faults disarmed + cool-down elapsed: the half-open probe takes
        # the primary again, succeeds, and the breaker closes
        clk[0] = 11.0
        assert srv.describe(plan)["breakers"][()] == "half-open"
        _check(srv.execute(plan, {}), t, keys, aggs, {},
               "recovered launch vs oracle")
        assert srv.guard_stats.breaker_recoveries == 1
        assert srv.describe(plan)["breakers"][()] == "closed"


# ---------------------------------------------------------------------------
# kernel / shard launch sites (wiring) + both-rungs-fail → BackendFailure
# ---------------------------------------------------------------------------


def _fused_aggcall_catalog():
    """A grouped AggCall in fused mode — the plan shape whose launch
    passes through core.executors._grouped_fused (GroupAgg roots take
    the engine's per-op path on CPU and never reach that site)."""
    from repro.core import (Assign, BinOp, Const, CursorLoop, Program, Var,
                            aggify, let)
    from repro.relational import Scan, Table
    from repro.relational.plan import AggCall
    prog = Program(
        "groupedMinMax", params=(),
        pre=[let("lo", Const(1e9)), let("hi", Const(-1e9))],
        loop=CursorLoop(
            Scan("PS", ("pk", "cost")),
            fetch=[("c", "cost")],
            body=[Assign("lo", BinOp("min", Var("lo"), Var("c"))),
                  Assign("hi", BinOp("max", Var("hi"), Var("c")))]),
        post=[], returns=("lo", "hi"))
    rp = aggify(prog)
    call = AggCall(rp.agg_call.child, rp.agg_call.aggregate,
                   rp.agg_call.param_binding, rp.agg_call.ordered,
                   rp.agg_call.sort_keys, rp.agg_call.sort_desc,
                   group_keys=("pk",), mode="fused")
    rng = np.random.default_rng(0)
    cat = {"PS": Table.from_columns(
        pk=np.sort(rng.integers(0, 13, 300)).astype(np.int32),
        cost=rng.uniform(1, 100, 300).astype(np.float32))}
    env = {"lo": np.float32(1e9), "hi": np.float32(-1e9)}
    return call, cat, env


def test_kernel_launch_site_wired():
    from repro.relational import execute
    call, cat, env = _fused_aggcall_catalog()
    with faults.inject("kernel_launch:1"):
        with pytest.raises(FaultInjected) as ei:
            execute(call, cat, env)
        assert ei.value.site == "kernel_launch"
    # exhausted: the same call now runs and the site costs nothing
    out = execute(call, cat, env)
    assert np.asarray(out.mask()).sum() == 13


def test_backend_failure_both_rungs_is_typed():
    """When the degraded jnp rung dies too (kernel_launch fires during
    its trace), the caller gets BackendFailure with the cause chained —
    never a raw exception."""
    call, cat, env = _fused_aggcall_catalog()
    srv = AggServer(cat, batch_window_s=0.0)
    with srv, faults.inject("backend_exc,kernel_launch"):
        with pytest.raises(BackendFailure) as ei:
            srv.execute(call, env)
        assert isinstance(ei.value.__cause__, FaultInjected)
    assert srv.guard_stats.backend_failures == 1
    assert srv.guard_stats.degraded_launches == 1


def test_shard_launch_site_wired():
    from repro.launch.sharded_agg import (sharded_fused_segment_agg,
                                          sharded_sortfree_segment_agg)
    with faults.inject("shard_launch:2"):
        with pytest.raises(FaultInjected) as ei:
            sharded_fused_segment_agg(
                np.zeros((4, 1)), np.zeros(4, np.int32),
                np.ones((4, 1), bool), 4, mesh=None)
        assert ei.value.site == "shard_launch"
        with pytest.raises(FaultInjected):
            sharded_sortfree_segment_agg(
                np.zeros((4, 1)), np.zeros((4, 1), np.uint32),
                np.ones((4, 1), bool), np.ones(4, bool), 4, 4, mesh=None)


# ---------------------------------------------------------------------------
# deadlines, backpressure, dispatcher supervision, drain
# ---------------------------------------------------------------------------


def test_deadline_shed_in_queue():
    srv, t, plan, keys, aggs, envs = _fresh(CASE_SMALL)
    with srv, faults.inject("dispatcher_stall:1"):
        fut = srv.submit(plan, {}, deadline=0.05)   # stall 0.25s > deadline
        err = fut.exception(timeout=120)
    assert isinstance(err, DeadlineExceeded)
    assert srv.guard_stats.deadline_shed == 1


def test_unexpired_deadline_serves():
    srv, t, plan, keys, aggs, envs = _fresh(CASE_SMALL)
    with srv:
        fut = srv.submit(plan, {}, deadline=300.0)
        _check(fut.result(timeout=120), t, keys, aggs, {},
               "deadline-ok vs oracle")
    assert srv.guard_stats.deadline_shed == 0


def test_queue_full_rejects_typed():
    srv, t, plan, keys, aggs, envs = _fresh(CASE_FILTERED, max_queue=2)
    with srv:
        # hold the launch lock so dequeued work blocks and the queue fills:
        # the dispatcher takes at most one batch (≤ max_queue requests)
        # before it blocks, so of 2 × max_queue + 2 submits at least two
        # must be turned away, however the threads interleave
        with srv._lock:
            futs = [srv.submit(plan, envs[i % len(envs)])
                    for i in range(6)]
        rejected = [f for f in futs
                    if isinstance(f.exception(timeout=120), QueueFull)]
        served = [f for f in futs if f not in rejected]
        assert rejected, "admission queue never pushed back"
        assert srv.guard_stats.queue_rejects == len(rejected)
        for f in served:
            assert f.result(timeout=120) is not None


def test_dispatcher_death_respawns_and_serves():
    srv, t, plan, keys, aggs, envs = _fresh(CASE_SMALL)
    with srv, faults.inject("dispatcher_die:1"):
        fut = srv.submit(plan, {})
        _check(fut.result(timeout=120), t, keys, aggs, {},
               "post-respawn launch vs oracle")
    assert srv.guard_stats.dispatcher_restarts == 1


def test_close_drains_under_load():
    srv, t, plan, keys, aggs, envs = _fresh(CASE_FILTERED)
    futs = [srv.submit(plan, envs[i % len(envs)]) for i in range(20)]
    srv.close(drain=True)
    for i, fut in enumerate(futs):
        env = envs[i % len(envs)]
        _check(fut.result(timeout=120), t, keys, aggs, env,
               f"drained request {i} vs oracle")
    with pytest.raises(ServerClosed):
        srv.submit(plan, envs[0])
    with pytest.raises(RuntimeError):       # legacy contract holds
        srv.submit(plan, envs[0])


def test_close_without_drain_fails_queue_typed():
    srv, t, plan, keys, aggs, envs = _fresh(CASE_SMALL)
    with faults.inject("dispatcher_stall:1"):
        futs = [srv.submit(plan, {}) for _ in range(3)]
        srv.close(drain=False)
    for fut in futs:
        assert isinstance(fut.exception(timeout=120), ServerClosed)


def test_close_drain_racing_ingest_commits_or_typed():
    """``close(drain=True)`` racing concurrent ``ingest`` calls: every
    ingest either commits IN FULL (its rows land and the resident state
    converges to them) or fails typed ``ServerClosed`` having changed
    nothing — never a half-committed append or torn resident state."""
    import jax.numpy as jnp
    from repro.relational import Table, execute
    from repro.relational.plan import GroupAgg, Scan

    rng = np.random.default_rng(21)
    cap, n0, nb = 1024, 256, 16
    cols = {"k": rng.integers(0, 30, cap).astype(np.int32),
            "v": rng.integers(-9, 9, cap).astype(np.float32)}
    t = Table({c: jnp.asarray(a) for c, a in cols.items()},
              jnp.asarray(np.arange(cap) < n0))
    plan = GroupAgg(Scan("T", ("k", "v")), ("k",),
                    (("s", "sum", "v"), ("c", "count", None)),
                    max_groups=64)
    srv = AggServer({"T": t})
    srv.snapshot(plan)                       # seed the residency
    outcomes = []

    def one(i):
        r = np.random.default_rng(100 + i)
        b = {"k": r.integers(0, 30, nb).astype(np.int32),
             "v": r.integers(-9, 9, nb).astype(np.float32)}
        try:
            outcomes.append(("ok", srv.ingest("T", b)))
        except ServerClosed:
            outcomes.append(("closed", None))

    threads = [threading.Thread(target=one, args=(i,)) for i in range(8)]
    for th in threads:
        th.start()
    time.sleep(0.002)
    srv.close(drain=True)
    for th in threads:
        th.join(timeout=120)
    assert len(outcomes) == 8
    committed = [o for o in outcomes if o[0] == "ok"]
    live = srv.table("T")
    # committed ingests landed in full; refused ones changed nothing
    assert int(np.asarray(live.mask()).sum()) == n0 + nb * len(committed)
    assert srv.stats.ingests == len(committed)
    # resident state never half-committed: snapshot == full recompute
    def groups(tab):
        out = tab.to_numpy()
        return {int(out["k"][i]): (float(out["s"][i]), float(out["c"][i]))
                for i in range(len(out["s"]))}
    assert groups(srv.snapshot(plan)) == \
        groups(execute(plan, {"T": live}))


def test_concurrent_load_with_faults_stays_correct():
    """Mixed chaos under concurrency: a dispatcher death and a backend
    failure mid-stream; every future still resolves to a typed error or
    a bit-correct result."""
    srv, t, plan, keys, aggs, envs = _fresh(CASE_FILTERED)
    results = {}

    def client(i):
        env = envs[i % len(envs)]
        fut = srv.submit(plan, env)
        try:
            results[i] = (env, fut.result(timeout=120))
        except ServeError as e:
            results[i] = (env, e)

    with srv, faults.inject("dispatcher_die:1,backend_exc:2"):
        threads = [threading.Thread(target=client, args=(i,))
                   for i in range(24)]
        for th in threads:
            th.start()
        for th in threads:
            th.join(timeout=180)
    assert len(results) == 24
    for i, (env, got) in results.items():
        if isinstance(got, ServeError):
            continue    # typed failure is an acceptable outcome
        _check(got, t, keys, aggs, env, f"chaos request {i} vs oracle")
