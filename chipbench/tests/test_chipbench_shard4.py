"""CPU rehearsal of the four-chip cell ``loops.sf30.shard4`` on four host
devices (a subprocess with
``XLA_FLAGS=--xla_force_host_platform_device_count=4``): the row-split
generator makes the rows ``tpch.generate`` makes, a whole traced run at
a tiny scale is correct with every launch on the shard-local route, and
at the cell's own shapes both loops take that route."""
import _paths
import json
import os
import subprocess
import sys

import pytest

CODE = r"""
import dataclasses, json, sys
import numpy as np, jax
sys.path[:0] = [%(root)r, %(src)r]
from chipbench import harness
from chipbench.data import loops, tpch, tpch_shard4
assert jax.device_count() == 4, jax.device_count()
out = {}

# the same rows as the one-device generator
a, b = tpch.generate(0.002, 2 ** 40 + 7), tpch_shard4.generate(0.002, 2 ** 40 + 7)
diff = {}
for t in a:
    for c in a[t].columns:
        x, y = np.asarray(a[t].columns[c]), np.asarray(b[t].columns[c])
        if not np.array_equal(x, y):
            diff[f"{t}.{c}"] = float(np.max(np.abs(x - y) / np.abs(x)))
out["differ"] = diff
out["split"] = {t: list(b[t].row_split[2]) for t in b if b[t].row_split}

# the cell's own shapes with the chip's kernel backend: the grid gate
# keeps both loops on the sorted route, which sorts shard by shard
import os
os.environ.pop("REPRO_GROUPAGG_SORTFREE")
os.environ["REPRO_SEGAGG_BACKEND"] = "pallas"
n = tpch.sizes(30)
plans = loops.build(a, ("Q18", "Q21"))
big = {}
for t, tab in b.items():
    rows = tab.columns[tpch.SCHEMAS[t][0]].sharding
    big[t] = dataclasses.replace(tab, columns={
        c: jax.ShapeDtypeStruct((n[t],), v.dtype, sharding=rows)
        for c, v in tab.columns.items()}, valid=None)
from repro.relational.engine import grouped_route
out["routes"] = {}
for q, (plan, params) in plans.items():
    plan = dataclasses.replace(
        plan, max_groups=n[loops.DOMAIN[q][0]])
    r = grouped_route(plan, big["LINEITEM"], params)
    out["routes"][q] = [r.name, r.merge, r.rows]

os.environ["REPRO_GROUPAGG_SORTFREE"] = "off"
del os.environ["REPRO_SEGAGG_BACKEND"]

# a whole traced run of the cell at a tiny scale
bench = json.load(open(%(bench)r))
res = harness.run(bench, "loops.sf30.shard4", 2 ** 35 + 17, 1.0, True,
                  config_override={"scale": 0.002}, log=lambda *a: None)
out["run"] = {"correct": res["correct"], "attempted": res["attempted"],
              "metrics": {k: v["value"] for k, v in res["metrics"].items()},
              "checks": res["checks"]}
print(json.dumps(out))
"""


@pytest.fixture(scope="module")
def rehearsal():
    env = {**os.environ, "JAX_PLATFORMS": "cpu",
           "XLA_FLAGS": (os.environ.get("XLA_FLAGS", "")
                         + " --xla_force_host_platform_device_count=4"),
           # on the CPU the sort-free route's grid gate passes at every
           # size; on the chip these plans take the sorted route (below)
           "REPRO_GROUPAGG_SORTFREE": "off"}
    src = os.path.join(_paths.ROOT, "src")
    code = CODE % {"root": _paths.ROOT, "src": src,
                   "bench": os.path.join(_paths.ROOT, "BENCHMARK.json")}
    p = subprocess.run([sys.executable, "-c", code], env=env,
                       capture_output=True, text=True, timeout=600)
    assert p.returncode == 0, p.stderr[-4000:]
    return json.loads(p.stdout.strip().splitlines()[-1])


def test_shard4_generator_makes_the_one_device_rows(rehearsal):
    # every column bit for bit but the one order whose line items
    # straddle a LINEITEM shard boundary: its total price adds its two
    # partial sums in another order
    assert set(rehearsal["differ"]) <= {"ORDERS.o_totalprice"}
    assert all(v < 1e-6 for v in rehearsal["differ"].values())
    assert rehearsal["split"] == {"LINEITEM": ["l_orderkey"],
                                  "ORDERS": ["o_orderkey"],
                                  "PARTSUPP": ["ps_partkey"]}


def test_shard4_cell_rehearsal_is_correct_on_the_shard_local_route(
        rehearsal):
    run = rehearsal["run"]
    assert run["correct"] is True, run["checks"]
    assert run["attempted"] > 0
    assert run["metrics"]["sharded_launch_share"] == 1.0
    assert run["metrics"]["reqs_per_launch"] == 1.0


def test_chip_shapes_take_the_shard_local_route(rehearsal):
    shard = 180_000_000 // 4
    assert rehearsal["routes"] == {"Q18": ["sorted", "ranges", shard],
                                   "Q21": ["sorted", "keys", shard]}


def test_dense_reference_counts_as_the_plain_one():
    """``tpch_loops_dense`` gives ``common``'s numbers: positions in a
    sparse and a dense key domain, and bad groups of right answers and
    of answers with keys served twice, missing, extra or wrong values."""
    import numpy as np
    from chipbench.reference import common, tpch_loops_dense as dense
    rng = np.random.default_rng(3)
    i = np.arange(1, 4001)
    for dom in ((((i >> 3) << 5) | (i & 7)).astype(np.int32),
                i.astype(np.int32), np.array([3, 10 ** 6], np.int32)):
        keys = np.concatenate([rng.choice(dom, 5000), [0, -7, dom[-1] + 1]]
                              ).astype(np.int32)
        assert np.array_equal(dense.positions(dom, keys),
                              common.positions(dom, keys))
        present = rng.random(len(dom)) < 0.7
        ref = rng.integers(0, 9, len(dom)).astype(np.float64)
        right = (dom[present], ref[present].astype(np.float32))
        wrong_value = (right[0], right[1] + (np.arange(len(right[1])) == 5))
        dup = (np.concatenate([right[0], right[0][:3]]),
               np.concatenate([right[1], right[1][:3]]))
        missing = (right[0][1:], right[1][1:])
        more = np.concatenate([dom[~present][:2], [-1]]).astype(np.int32)
        extra = (np.concatenate([right[0], more]),
                 np.concatenate([right[1], np.zeros(len(more), np.float32)]))
        shuffled = tuple(a[::-1] for a in right)
        for k, v in (right, wrong_value, dup, missing, extra, shuffled):
            assert dense.bad_groups(k, v, dom, present, ref) == \
                common.bad_groups(k, v, dom, present, ref)
