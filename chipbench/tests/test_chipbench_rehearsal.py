"""CPU rehearsal of the harness's parts at a tiny scale: the traffic
schedules, the copied references against the system on its jnp and
interpret backends, and that a new configuration, cell, traffic or
metric is found by name without editing any file."""
import _paths
import hashlib
import json
import os
import shutil
import subprocess
import sys

import numpy as np
import pytest

from chipbench import harness, loadgen
from chipbench.data import tpch

BENCH = _paths.bench()


def _traffic(name):
    return harness.load_json(os.path.join(harness.HERE, "traffic",
                                          f"{name}.json"))


def test_cycle_schedule_is_fixed_and_staggered():
    s = _traffic("closed4_cycle")["streams"][0]
    a = loadgen.client_sequences(s, tpch.host_rng(1, 1))
    b = loadgen.client_sequences(s, tpch.host_rng(2 ** 40 + 9, 1))
    firsts = [(seq[0].plan, seq[0].params.get("lb")) for seq in a]
    assert firsts == [("Q18", None), ("Q2", 10), ("Q21", None),
                      ("Q13", None)]
    assert [[(r.plan, r.params) for r in seq] for seq in a] == \
        [[(r.plan, r.params) for r in seq] for seq in b]
    assert all(len(seq) == 16 for seq in a)


def test_mix_schedule_has_the_same_work_for_every_seed():
    s = {"name": "m", "type": "closed", "clients": 8, "length": 64,
         "mix": [{"plan": "Q2", "share": 0.5,
                  "params": {"lb": {"int_uniform": [4, 50]}}},
                 {"plan": "Q18", "share": 0.5}]}
    a = loadgen.client_sequences(s, tpch.host_rng(3, 1))
    b = loadgen.client_sequences(s, tpch.host_rng(4, 1))
    again = loadgen.client_sequences(s, tpch.host_rng(3, 1))
    assert [[(r.plan, r.params) for r in q] for q in a] == \
        [[(r.plan, r.params) for r in q] for q in again]
    assert len(a) == 8
    for qa, qb in zip(a, b):
        assert sorted(r.plan for r in qa) == sorted(r.plan for r in qb)
        assert sum(r.plan == "Q2" for r in qa) == 32
        assert all(4 <= r.params["lb"] <= 50
                   for r in qa if r.plan == "Q2")
    assert [r.plan for r in a[0]] != [r.plan for r in b[0]]


def test_open_streams_are_refused():
    with pytest.raises(ValueError):
        loadgen.client_sequences({"name": "w", "type": "open",
                                  "rate_per_s": 1.0}, tpch.host_rng(1, 1))


def test_generator_follows_the_spec():
    """Keys and values as TPC-H Clause 4.2.3 populates them."""
    n = tpch.sizes(0.01)
    cat = tpch.generate(0.01, 2 ** 36 + 11)
    h = {t: {c: np.asarray(a) for c, a in cat[t].columns.items()}
         for t in cat}
    assert {t: len(h[t][tpch.SCHEMAS[t][0]]) for t in h} == n
    assert all(set(h[t]) == set(tpch.SCHEMAS[t]) for t in h)
    p, s, ps, c, o, li = (h[t] for t in ("PART", "SUPPLIER", "PARTSUPP",
                                         "CUSTOMER", "ORDERS", "LINEITEM"))
    assert (p["p_partkey"] == np.arange(1, n["PART"] + 1)).all()
    assert (c["c_custkey"] == np.arange(1, n["CUSTOMER"] + 1)).all()
    ok = o["o_orderkey"]
    assert ok[:9].tolist() == [1, 2, 3, 4, 5, 6, 7, 32, 33]
    assert (np.diff(ok) > 0).all() and ((ok % 32) < 8).all()
    assert (o["o_custkey"] % 3 != 0).all()
    assert o["o_custkey"].min() >= 1 and \
        o["o_custkey"].max() <= n["CUSTOMER"]
    # the four suppliers of each part, distinct, by the spec's formula
    S = n["SUPPLIER"]
    pk = ps["ps_partkey"].astype(np.int64)
    i = np.tile(np.arange(4), n["PART"])
    assert (ps["ps_suppkey"] == (pk + i * (S // 4 + (pk - 1) // S)) % S
            + 1).all()
    assert (np.unique(ps["ps_suppkey"].reshape(-1, 4), axis=1).shape[1]
            == 4)
    four = ps["ps_suppkey"].reshape(-1, 4)[li["l_partkey"] - 1]
    assert (four == li["l_suppkey"][:, None]).any(axis=1).all()
    cost = ps["ps_supplycost"]
    assert cost.min() >= 1.0 and cost.max() <= 1000.0
    retail = (90000 + (p["p_partkey"] // 10) % 20001
              + 100 * (p["p_partkey"] % 1000)) / 100
    np.testing.assert_allclose(p["p_retailprice"], retail, rtol=1e-6)
    np.testing.assert_allclose(
        li["l_extendedprice"],
        li["l_quantity"] * retail[li["l_partkey"] - 1], rtol=1e-6)
    # dates from the order date, flags from the current date
    odate = o["o_orderdate"][np.searchsorted(ok, li["l_orderkey"])]
    assert o["o_orderdate"].max() <= tpch.END_DAY - 151
    assert ((li["l_shipdate"] - odate >= 1)
            & (li["l_shipdate"] - odate <= 121)).all()
    assert ((li["l_commitdate"] - odate >= 30)
            & (li["l_commitdate"] - odate <= 90)).all()
    gap = li["l_receiptdate"] - li["l_shipdate"]
    assert ((gap >= 1) & (gap <= 30)).all()
    assert (li["l_linestatus"]
            == (li["l_shipdate"] > tpch.CURRENT_DAY)).all()
    assert ((li["l_returnflag"] == 0)
            == (li["l_receiptdate"] > tpch.CURRENT_DAY)).all()
    counts = np.bincount(np.searchsorted(ok, li["l_orderkey"]))
    assert sorted(set(counts.tolist())) == list(range(1, 8))


@pytest.fixture(params=["jnp", "interpret"])
def backend(request, monkeypatch):
    monkeypatch.setenv("REPRO_SEGAGG_BACKEND", request.param)
    monkeypatch.setenv("REPRO_GROUPAGG_FUSED", request.param)
    return request.param


def _served(cfg_name, names, scale, params_of=lambda n: {}):
    from repro.serve import AggServer
    cfg = harness.load_json(os.path.join(
        _paths.ROOT, "chipbench", "configs", f"{cfg_name}.json"))
    data = harness.load_module("data", cfg["data"])
    plans_mod = harness.load_module("data", cfg["plans"])
    ref_mod = harness.load_module("reference", cfg["reference"])
    cat = data.generate(scale, 2 ** 35 + 1)
    plans = plans_mod.build(cat, names)
    host = {t: {c: np.asarray(a) for c, a in cat[t].columns.items()}
            for t in cat}
    ref = ref_mod.Reference(host, plans_mod.result_columns)
    with AggServer(cat, max_batch=4) as srv:
        for name in names:
            plan, base = plans[name]
            for extra in params_of(name):
                params = harness._cast_params(base, extra)
                got = srv.execute(plan, params or None).to_numpy()
                yield name, ref.check(name, {**base, **extra}, got)


def test_loop_references_equal_the_system(backend):
    lbs = {"Q2": [{"lb": 4}, {"lb": 50}]}
    for name, nums in _served("tpch_sf10_loops",
                              ["Q2", "Q13", "Q18", "Q21"], 0.0003,
                              lambda n: lbs.get(n, [{}])):
        assert nums == {"bad_groups": 0}, (backend, name, nums)


def _digest(root):
    out = {}
    for d, _dirs, files in os.walk(root):
        for f in files:
            if f.endswith((".py", ".json")):
                p = os.path.join(d, f)
                out[os.path.relpath(p, root)] = hashlib.sha256(
                    open(p, "rb").read()).hexdigest()
    return out


def test_additions_are_found_by_name_without_edits(tmp_path):
    """A configuration, a traffic mix, a metric and a cell are added as
    new files and new BENCHMARK.json entries; the copy's run finds them,
    and no file that was there changed."""
    root = tmp_path / "checkout"
    shutil.copytree(os.path.join(_paths.ROOT, "chipbench"),
                    root / "chipbench",
                    ignore=shutil.ignore_patterns("__pycache__", "tests"))
    before = _digest(root / "chipbench")
    bench = json.loads(json.dumps(BENCH))
    cfg = json.load(open(root / "chipbench/configs/tpch_sf10_loops.json"))
    cfg.update(name="tpch_tiny_q18", scale=0.0005,
               limits={"Q18.bad_groups": 0})
    (root / "chipbench/configs/tpch_tiny_q18.json").write_text(
        json.dumps(cfg))
    (root / "chipbench/traffic/one_q18.json").write_text(json.dumps(
        {"streams": [{"name": "c", "type": "closed", "clients": 1,
                      "cycle": [{"plan": "Q18"}]}]}))
    (root / "chipbench/metrics/answers_per_s.py").write_text(
        "def read(run):\n    return len(run.requests()) / run.window_s\n")
    bench["configs"].append(
        {"name": "tpch_tiny_q18", "source": "test", "reduced": [],
         "file": "chipbench/configs/tpch_tiny_q18.json", "why": "test"})
    bench["workloads"].append(
        {"name": "tiny.q18", "config": "tpch_tiny_q18", "traffic": "one_q18",
         "chips": 1, "why": "test"})
    bench["end_to_end"].append(
        {"name": "answers_per_s", "unit": "1/s", "better": "higher",
         "bound": 0.05, "source": "host_clock", "workloads": ["tiny.q18"]})
    (root / "BENCHMARK.json").write_text(json.dumps(bench))
    code = (
        "import json, sys; sys.path[:0] = ['.', %r];"
        "from chipbench import harness;"
        "b = json.load(open('BENCHMARK.json'));"
        "print(json.dumps(harness.run(b, 'tiny.q18', 5, 0.5, False)))"
        % os.path.join(_paths.ROOT, "src"))
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    p = subprocess.run([sys.executable, "-c", code], cwd=root, env=env,
                       capture_output=True, text=True, timeout=600)
    assert p.returncode == 0, p.stderr[-3000:]
    out = json.loads(p.stdout.strip().splitlines()[-1])
    assert out["correct"] and out["metrics"]["answers_per_s"]["value"] > 0
    assert set(out["metrics"]) == {"answers_per_s", "setup_s"}
    assert _digest(root / "chipbench") == {
        **before, **{k: v for k, v in _digest(root / "chipbench").items()
                     if k not in before}}
