"""Concurrency stress for the aggregate-serving layer.

An 8-way thread pool hammers ONE server with mixed-shape parameterized
requests (two plans × a parameter pool, sync ``execute`` and batched
``submit`` interleaved) and asserts:

* NO retrace storm — the trace counter stays within the number of
  distinct shape buckets (plan × batch-size bucket), however the racing
  requests happen to coalesce;
* slot tables build once per (table version, key set, bucket) no matter
  how many threads contend;
* results are deterministic: every response equals the fresh
  single-threaded reference.

The sharded variant reuses the subprocess 8-way host-mesh pattern of
test_sharded_segment_agg.py: a row-sharded catalog table is served
through the cached GLOBAL slot assignment (the provide_slots override
bypasses the per-shard launcher), stays bit-identical to the unsharded
reference, and still slots exactly once."""
import math
import os
import subprocess
import sys
from concurrent.futures import ThreadPoolExecutor

import numpy as np

from repro.relational import Table, execute
from repro.relational.plan import Filter, GroupAgg, Scan
from repro.serve import AggServer

from repro.core.loop_ir import Col, Var

SCHEMA = ("k", "v")


def _catalog():
    rng = np.random.default_rng(11)
    n = 2048
    return {"T": Table.from_columns(
        k=rng.integers(0, 40, n).astype(np.int32),
        v=rng.integers(-3, 4, n).astype(np.float32))}


def _plans():
    child = Filter(Scan("T", SCHEMA), Col("v") >= Var("lo"))
    scan = Scan("T", SCHEMA)
    return (
        # parameterized tiles (Filter child → slots derive in-trace)
        GroupAgg(child, ("k",), (("s", "sum", "v"), ("c", "count", None)),
                 max_groups=48),
        GroupAgg(child, ("k",), (("mx", "max", "v"), ("mn", "min", "v")),
                 max_groups=200),
        # scan tiles (Scan child → server-cached slot tables; the two
        # declared bounds bucket differently → two slot builds total)
        GroupAgg(scan, ("k",), (("s", "sum", "v"), ("c", "count", None)),
                 max_groups=48),
        GroupAgg(scan, ("k",), (("mx", "max", "v"), ("mn", "min", "v")),
                 max_groups=200),
    )


def _norm(t: Table) -> dict:
    out = t.to_numpy()
    keys = np.argsort(out["k"], kind="stable")
    return {c: tuple(np.asarray(v)[keys].tolist()) for c, v in out.items()}


def test_threadpool_stress_no_retrace_storm_deterministic():
    cat = _catalog()
    plans = _plans()
    params = [{"lo": float(x)} for x in (-3.0, -1.0, 0.0, 1.0, 2.0)]
    work_params = {i: (params if i < 2 else [{}])
                   for i in range(len(plans))}
    ref = {(i, p.get("lo")): _norm(execute(plans[i], cat, p))
           for i, ps in work_params.items() for p in ps}

    max_batch = 8
    srv = AggServer(cat, max_batch=max_batch, batch_window_s=0.001)
    rng = np.random.default_rng(0)
    work = []
    for i in rng.integers(0, len(plans), 200):
        ps = work_params[int(i)]
        work.append((int(i), ps[rng.integers(0, len(ps))]))

    def worker(chunk):
        got = []
        for n, (i, p) in enumerate(chunk):
            if n % 4 == 0:     # mix the serialized sync path in
                got.append(((i, p.get("lo")), _norm(srv.execute(plans[i], p))))
            else:
                got.append(((i, p.get("lo")),
                            srv.submit(plans[i], p)))
        return got

    with ThreadPoolExecutor(max_workers=8) as pool:
        chunks = [work[i::8] for i in range(8)]
        results = [r for f in [pool.submit(worker, c) for c in chunks]
                   for r in f.result()]
    srv.close()

    for key, got in results:
        if not isinstance(got, dict):
            got = _norm(got.result(timeout=120))
        assert got == ref[key], f"nondeterministic result for {key}"

    # retrace storm check: traces bounded by distinct shape buckets =
    # parameterized plans × batch-size buckets ({1,2,4,8} under
    # max_batch=8) + one bucket per parameterless scan tile, NOT by the
    # 200 requests
    buckets = int(math.log2(max_batch)) + 1
    assert srv.stats.traces <= 2 * buckets + 2
    # one slot table per (table version, key set, bucket): the two scan
    # tiles declare different buckets, so exactly two builds however 8
    # threads contend
    assert srv.stats.slot_builds == 2
    assert srv.stats.requests == 200


def test_sharded_serving_in_subprocess_8way_mesh():
    code = """
import numpy as np, jax, jax.numpy as jnp
assert jax.device_count() == 8, jax.device_count()
from jax.sharding import Mesh
from repro.relational import Table, execute
from repro.relational.plan import GroupAgg, Scan
from repro.serve import AggServer
import repro.launch.sharded_agg as sa

mesh = Mesh(np.asarray(jax.devices()).reshape(8), ("data",))
rng = np.random.default_rng(7)
n = 4096
t = Table.from_columns(k=rng.integers(0, 37, n).astype(np.int32),
                       v=rng.integers(-40, 40, n).astype(np.float32))
plan = GroupAgg(Scan("T", ("k", "v")), ("k",),
                (("s", "sum", "v"), ("c", "count", None),
                 ("mx", "max", "v")), max_groups=64)
want = execute(plan, {"T": t}).to_numpy()

launcher_calls, shard_calls = [], []
orig = sa.sharded_sortfree_segment_agg
sa.sharded_sortfree_segment_agg = lambda *a, **k: (launcher_calls.append(1),
                                                   orig(*a, **k))[1]
orig_fused = sa.sharded_fused_segment_agg
sa.sharded_fused_segment_agg = lambda *a, **k: (
    shard_calls.append(k.get("layout")), orig_fused(*a, **k))[1]
srv = AggServer({"T": t.shard_rows(mesh, "data")})
outs = [srv.execute(plan) for _ in range(3)]
# stable cross-call global slot assignment: one build, one trace, and the
# per-shard slotting launcher never runs — each shard aggregates onto the
# cached global slots under shard_map (a Mosaic kernel cannot be
# partitioned by GSPMD)
assert srv.stats.slot_builds == 1, srv.stats
assert srv.stats.traces == 1, srv.stats
assert not launcher_calls, "cached-slot serving must bypass the launcher"
assert shard_calls == ["unsorted"], shard_calls
o0 = outs[0].to_numpy()
for o in outs[1:]:
    on = o.to_numpy()
    assert all(np.array_equal(on[k], o0[k]) for k in on)
order = np.argsort(o0["k"], kind="stable")
worder = np.argsort(want["k"], kind="stable")
for k in want:
    assert np.array_equal(np.asarray(want[k])[worder],
                          np.asarray(o0[k])[order]), k
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
