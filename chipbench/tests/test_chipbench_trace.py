"""Trace reduction against a small trace recorded on one TPU v5e
(``chipbench/tools/record_trace.py``: the four TPC-H loops of
``loops.closed4`` served once each inside the ``chipbench.window``
span)."""
import _paths  # noqa: F401
import gzip
import os
import shutil
from types import SimpleNamespace

import pytest

from chipbench import trace_reduce
from chipbench.harness import load_module

FIXTURE = os.path.join(os.path.dirname(os.path.abspath(__file__)), "data",
                       "small.xplane.pb.gz")


@pytest.fixture(scope="module")
def trace(tmp_path_factory):
    path = tmp_path_factory.mktemp("trace") / "small.xplane.pb"
    with gzip.open(FIXTURE) as src, open(path, "wb") as dst:
        shutil.copyfileobj(src, dst)
    return trace_reduce.reduce(str(path))


def _naive_union(ivs, lo, hi):
    pts = sorted((max(s, lo), min(e, hi)) for s, e in ivs
                 if min(e, hi) > max(s, lo))
    total, cur_s, cur_e = 0, None, None
    for s, e in pts:
        if cur_e is None or s > cur_e:
            if cur_e is not None:
                total += cur_e - cur_s
            cur_s, cur_e = s, e
        else:
            cur_e = max(cur_e, e)
    return total + (cur_e - cur_s if cur_e is not None else 0)


def test_one_device_and_the_window_span(trace):
    assert trace.devices == 1
    lo, hi = trace.window
    assert 0 < trace.window_s < 5
    inside = [o for o in trace.ops if lo <= o.start < hi]
    assert inside, "device ops fall inside the host window span"


def test_busy_union_and_idle_share(trace):
    lo, hi = trace.window
    want = _naive_union([(o.start, o.end) for o in trace.ops], lo, hi)
    assert trace.busy_s() == pytest.approx(want * 1e-9)
    assert 0 < trace.busy_s() < trace.window_s
    idle = load_module("metrics", "idle_pct.scan").read(
        SimpleNamespace(trace=trace))
    assert idle == pytest.approx(100 * (1 - want * 1e-9 / trace.window_s))
    assert 0 < idle < 100


def test_sort_and_kernel_attribution(trace):
    sorts = [o for o in trace.ops if o.category == "sort"]
    kernels = [o for o in trace.ops if o.category == "kernel"]
    assert sorts and all(" sort(" in o.name for o in sorts)
    assert kernels and all("tpu_custom_call" in o.name for o in kernels)
    assert any("_segment_agg_pallas" in o.name for o in kernels)
    assert trace.category_s("sort") > 0 and trace.category_s("kernel") > 0
    total = sum(trace.category_s(c) for c in ("sort", "kernel", "other"))
    assert total == pytest.approx(trace.busy_s())
    top = trace.top_ops(10)
    assert len(top) == 10 and top[0][1] >= top[-1][1] > 0


@pytest.mark.parametrize("metric,category", [("sort_ms", "sort"),
                                              ("other_ops_ms", "other")])
def test_per_request_device_time(trace, metric, category):
    read = load_module("metrics", metric).read
    done = [object()] * 4
    run = SimpleNamespace(trace=trace, requests=lambda: done)
    assert read(run) == pytest.approx(trace.category_s(category) * 1e3 / 4)
    assert read(SimpleNamespace(trace=None, requests=lambda: done)) is None
    assert read(SimpleNamespace(trace=trace, requests=lambda: [])) is None


def test_idle_gaps_are_named_and_ordered(trace):
    gaps = trace.idle_gaps(lambda s, e: "gap", 5)
    assert [g[0] for g in gaps] == ["gap"] * 5
    assert all(a[1] >= b[1] for a, b in zip(gaps, gaps[1:]))
    assert sum(g for _n, g in trace.idle_gaps(lambda s, e: "", 10 ** 6)) == \
        pytest.approx(trace.window_s - trace.busy_s())


def test_own_time_counts_nested_ops_once():
    """A ``while`` spans the ops of its body: each instant goes to the
    innermost op, so the classes add up to the busy time."""
    Op = trace_reduce.Op
    ops = [Op("%while.1 = while", 0, 100, "other", 0),
           Op("%sort.1 = sort", 10, 30, "sort", 0),
           Op("%k = custom-call", 40, 60, "kernel", 0),
           Op("%f = fusion", 90, 120, "other", 0),     # overlaps the end
           Op("%g = fusion", 200, 210, "other", 0),
           Op("%sort.2 = sort", 0, 50, "sort", 1)]
    pieces = trace_reduce.own_time(ops)
    own = {}
    for s, e, o in pieces:
        own[o.name] = own.get(o.name, 0) + e - s
    assert own == {"%while.1 = while": 50, "%sort.1 = sort": 20,
                   "%k = custom-call": 20, "%f = fusion": 30,
                   "%g = fusion": 10, "%sort.2 = sort": 50}
    tr = trace_reduce.Trace((0, 300), ops, 2)
    total = sum(tr.category_s(c) for c in ("sort", "kernel", "other"))
    assert total == pytest.approx(130e-9 + 50e-9)
    assert dict(tr.top_ops(10))["%while.1 = while"] == pytest.approx(50e-9)


@pytest.mark.parametrize("name,op,cls", [
    ("%sort.3 = (s32[8]{0}, s32[8]{0}) sort(s32[8]{0} %a, s32[8]{0} %b), "
     "dimensions={0}", "sort", "sort"),
    ("%k = f32[12,6144]{1,0:T(8,128)} custom-call(f32[3,8]{1,0} %x), "
     'custom_call_target="tpu_custom_call"', "custom-call", "kernel"),
    ("%sort_fusion = s32[8]{0} fusion(s32[8]{0} %a), kind=kLoop",
     "fusion", "other"),
    ("%c = s32[757]{0} custom-call(), "
     'custom_call_target="AllocateBuffer"', "custom-call", "other"),
    ("jit_run(123)", "", "other"),
])
def test_opcode_classification(name, op, cls):
    assert trace_reduce.opcode(name) == op
    assert trace_reduce.classify(name) == cls
