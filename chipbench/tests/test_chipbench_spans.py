"""The program's spans and scopes in a profiler trace (``chipbench.spans``:
the server's ``agg.*`` and the relational layer's ``table.*`` host spans,
each device op's innermost ``jax.named_scope``), the numbers read from
them (``guard_scan_ms``, ``idle_pct_dispatch``, ``gather_ms``), and the
``queue_wait_ms`` metric's reader.

The served-trace test runs on the CPU; the fixture
``data/spans.xplane.pb.gz`` was recorded on one TPU v5e by
``chipbench/tools/record_trace.py`` (the four loops of ``loops.closed4``
served once each through ``AggServer.execute``)."""
import _paths  # noqa: F401
import gzip
import importlib.util
import json
import os
import shutil
import sys
from types import SimpleNamespace

import jax
import pytest

from chipbench import spans, trace_reduce
from chipbench.harness import load_module
from chipbench.spans import Span, Traced
from chipbench.trace_reduce import Op, Trace
from chipbench.xplane_scopes import scope_of

DATA = os.path.join(os.path.dirname(os.path.abspath(__file__)), "data")

#: the spans one guarded launch of a parameterized plan opens inside
#: ``agg.batch``
LAUNCH_SPANS = ("agg.prepare", "agg.args", "agg.dispatch", "agg.await",
                "agg.guard_scan", "agg.unbatch")


def _unzip(tmp_path_factory, name):
    path = tmp_path_factory.mktemp("trace") / name
    with gzip.open(os.path.join(DATA, name + ".gz")) as src, \
            open(path, "wb") as dst:
        shutil.copyfileobj(src, dst)
    return str(path)


def _inside(span, outer):
    return (outer.thread == span.thread and outer.start <= span.start
            and span.end <= outer.end)


# -- a server traced on the CPU ------------------------------------------


@pytest.fixture(scope="module")
def served(tmp_path_factory):
    """Q2 (a parameter, a join) and Q13 through ``serve_async``, two
    requests each, under the profiler as the harness starts it."""
    from chipbench.data import loops, tpch
    from repro.serve import AggServer, ServeRequest

    cat = tpch.generate(0.001, 5)
    plans = loops.build(cat, ["Q2", "Q13"])
    srv = AggServer(cat, max_batch=1)
    for plan, params in plans.values():             # compile outside
        srv.execute(plan, params)
    stats0 = srv._stats_copy()
    tdir = str(tmp_path_factory.mktemp("prof"))
    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    opts.host_tracer_level = 1
    jax.profiler.start_trace(tdir, profiler_options=opts)
    with jax.profiler.TraceAnnotation(trace_reduce.WINDOW_SPAN):
        futs = [srv.serve_async(ServeRequest(plan, params or None))
                for _ in range(2) for plan, params in plans.values()]
        for f in futs:
            f.result().table.to_numpy()
    jax.profiler.stop_trace()
    stats1 = srv._stats_copy()
    srv.close()
    names = {plan.aggregate.name: q for q, (plan, _p) in plans.items()}
    return (spans.reduce(trace_reduce.find_xplane(tdir)), names,
            stats0, stats1)


def test_served_spans_carry_plan_and_request_ids(served):
    tr, names, _s0, _s1 = served
    batches = [s for s in tr.spans if s.name == "agg.batch"]
    assert len(batches) == 4
    assert sorted(names[s.fields["plan"]] for s in batches) == \
        ["Q13", "Q13", "Q2", "Q2"]
    ids = [int(s.fields["reqs"]) for s in batches]
    assert len(set(ids)) == 4
    for b in batches:
        inner = [s for s in tr.spans if s is not b and _inside(s, b)]
        got = {s.name for s in inner}
        want = set(LAUNCH_SPANS) | {"agg.deliver"}
        if names[b.fields["plan"]] == "Q13":
            want -= {"agg.args", "agg.unbatch"}     # no parameters
        assert want <= got, (b.fields, got)
        for s in inner:
            assert s.fields == b.fields
    for s in tr.spans:
        if s.name.startswith("agg.") and s.name not in ("agg.batch",
                                                        "agg.coalesce"):
            assert any(_inside(s, b) for b in batches), s
    assert any(s.name == "agg.coalesce" for s in tr.spans)
    assert sum(s.name == "table.to_host" for s in tr.spans) >= 4


def test_queue_wait_counter_and_readers_on_a_served_trace(served):
    tr, _names, s0, s1 = served
    assert s1.queue_wait_s > s0.queue_wait_s
    run = SimpleNamespace(trace=tr.trace, stats0=s0, stats1=s1)
    wait = load_module("metrics", "queue_wait_ms").read(run)
    assert wait == pytest.approx((s1.queue_wait_s - s0.queue_wait_s)
                                 * 1e3 / (s1.requests - s0.requests))
    scan = spans.guard_scan_ms(tr)
    scans = [s for s in tr.spans if s.name == "agg.guard_scan"]
    assert len(scans) == 4 and scan == pytest.approx(
        sum(s.end - s.start for s in scans) * 1e-6 / 4)


# -- the readers on hand-built traces -------------------------------------


def _run(trace=None, done=(), stats0=None, stats1=None):
    return SimpleNamespace(trace=trace, requests=lambda: list(done),
                           stats0=stats0, stats1=stats1)


def test_queue_wait_ms_reader():
    read = load_module("metrics", "queue_wait_ms").read
    s0 = SimpleNamespace(requests=10, queue_wait_s=2.0)
    s1 = SimpleNamespace(requests=14, queue_wait_s=14.0)
    assert read(_run(stats0=s0, stats1=s1)) == pytest.approx(3000.0)
    assert read(_run(stats0=s0, stats1=s0)) is None       # no request
    assert read(_run(stats1=s1)) is None
    old = SimpleNamespace(requests=10)      # a server without the counter
    assert read(_run(stats0=old, stats1=SimpleNamespace(requests=14))) \
        is None


def test_guard_scan_ms_reader():
    got = [Span("agg.guard_scan", 100, 300, 1),
           Span("agg.guard_scan", 500, 900, 1),
           Span("agg.guard_scan", 2000, 2100, 1),       # after the window
           Span("agg.await", 0, 100, 1)]
    assert spans.guard_scan_ms(Traced(Trace((0, 1000), [], 1), got)) == \
        pytest.approx(300e-6)
    assert spans.guard_scan_ms(Traced(Trace((0, 1000), [], 1))) is None


def test_idle_pct_dispatch_reader():
    """Host work is the own time of every ``agg.*`` span but the waits;
    the device busy inside it is not idle."""
    ops = [Op("%f = fusion", 0, 400, "other", 0),
           Op("%g = fusion", 600, 650, "other", 0)]
    got = [Span("agg.batch", 100, 900, 1),
           Span("agg.dispatch", 150, 200, 1),       # device busy: 0 idle
           Span("agg.await", 200, 500, 1),          # waiting: excluded
           Span("agg.guard_scan", 500, 700, 1),     # 150 of it idle
           Span("agg.coalesce", 950, 1000, 1),      # sleeping: excluded
           Span("table.to_host", 0, 1000, 2)]       # not the dispatcher
    read = spans.idle_pct_dispatch
    # batch's own time: 100-150 busy, 700-900 idle; guard scan: 500-600
    # idle, 600-650 busy, 650-700 idle
    assert read(Traced(Trace((0, 1000), ops, 1), got)) == \
        pytest.approx(100.0 * (200 + 100 + 50) / 1000)
    assert read(Traced(Trace((0, 1000), ops, 1))) is None
    assert read(Traced(Trace((0, 1000), [], 0), got)) is None


def test_gather_ms_reader():
    ops = [Op("%w = while", 0, 1000, "other", 0),
           Op("%s = sort", 100, 300, "sort", 0),
           Op("%g = fusion", 400, 600, "other", 0),
           Op("%h = fusion", 900, 1200, "other", 0)]
    scopes = ["sort_gather", "group_sort", "sort_gather", "sort_gather"]
    t = Traced(Trace((0, 1100), ops, 1), [], scopes)
    # the while's own 500 ns, the fusion's 200, and 200 of the last
    # inside the window (the sort is not a gather)
    assert spans.gather_ms(t, 2) == pytest.approx(900e-6 / 2)
    assert spans.gather_ms(t, 0) is None
    assert spans.gather_ms(
        Traced(Trace((0, 1100), ops[1:2], 1), [], scopes[1:2]), 1) is None


def test_open_span_labels_a_gap():
    got = [Span("agg.batch", 100, 900, 1, {"plan": "q"}),
           Span("agg.await", 200, 500, 1, {"plan": "q"}),
           Span("table.to_host", 0, 1000, 2)]
    t = Traced(Trace((0, 1000), [], 1), got)
    assert spans.open_span(t, 300, 400) == "agg.await plan=q"
    assert spans.open_span(t, 600, 700) == "agg.batch plan=q"
    assert spans.open_span(t, 950, 990) == "no span open"


@pytest.mark.parametrize("op_name,scope", [
    ("jit(run)/vmap(sort_gather)/jit(_take)/gather:", "sort_gather"),
    ("jit(run)/vmap(group_sort)/sort:", "group_sort"),
    ("jit(run)/vmap(join.probe)/while/body/keyslot.probe/add",
     "keyslot.probe"),
    ("jit(run)/vmap(join.probe)/while/cond/lt", "join.probe"),
    ("jit(f)/resident.fold/jit(_take)/vmap(a/b)/mul", "b"),
    ("jit(run)/vmap(jit(_take))/gather:", None),
    ("jit(run)/vmap()/while/body/closed_call/gather", None),
    ("jit(run)/vmap()/add;jit(run)/vmap(group_sort)/add", None),
    ("args[0]:", None),
    ("reduce_window_sum", None),
])
def test_scope_of(op_name, scope):
    assert scope_of(op_name) == scope


# -- recorded traces ---------------------------------------------------------


def test_small_fixture_reduces_as_before(tmp_path_factory):
    """The reduction of the earlier recording (``small.xplane.pb.gz``,
    which has no spans and no named scopes) reads exactly as
    ``small.reduced.json`` records it, and the spans' reading of it finds
    nothing."""
    path = _unzip(tmp_path_factory, "small.xplane.pb")
    tr = trace_reduce.reduce(path)
    with open(os.path.join(DATA, "small.reduced.json")) as f:
        want = json.load(f)
    assert list(tr.window) == want["window"]
    assert (tr.devices, len(tr.ops)) == (want["devices"], want["ops"])
    assert tr.busy_s() == want["busy_s"]
    assert {c: tr.category_s(c) for c in ("sort", "kernel", "other")} == \
        want["category_s"]
    assert tr.top_ops(10) == want["top_ops"]
    assert [g for _l, g in tr.idle_gaps(lambda s, e: "", 10)] == \
        want["idle_gaps"]
    t = spans.reduce(path)
    assert t.spans == [] and t.scopes == [None] * len(tr.ops)
    assert t.trace.busy_s() == want["busy_s"]


@pytest.fixture(scope="module")
def recorded(tmp_path_factory):
    path = _unzip(tmp_path_factory, "spans.xplane.pb")
    return path, spans.reduce(path)


def test_recorded_spans_nest_under_their_batch(recorded):
    _path, t = recorded
    lo, hi = t.trace.window
    batches = [s for s in t.spans if s.name == "agg.batch"]
    assert len(batches) == 4 and all(lo <= b.start < hi for b in batches)
    assert len({b.fields["reqs"] for b in batches}) == 4
    assert {b.fields["plan"] for b in batches} == {
        "orderQty_agg", "minCostSupp_agg", "lateCount_agg",
        "orderCount_agg"}
    for b in batches:
        inner = [s for s in t.spans if s is not b and _inside(s, b)]
        assert set(LAUNCH_SPANS) <= {s.name for s in inner}
        assert all(s.fields == b.fields for s in inner)
    assert all(any(_inside(s, b) for b in batches) for s in t.spans)


def test_recorded_ops_carry_their_scopes(recorded):
    """Every event of the ``XLA Ops`` line is matched with its metadata,
    and the program's scopes and kernel names come through."""
    path, t = recorded
    from chipbench.xplane_scopes import line_scopes
    named = line_scopes(path, trace_reduce.OPS_LINE)["/device:TPU:0"]
    assert [n for n, _s in named] == [o.name for o in t.trace.ops]
    scope = dict(zip(map(id, t.trace.ops), t.scopes))
    sorts = [o for o in t.trace.ops if o.category == "sort"]
    kernels = [o for o in t.trace.ops if o.category == "kernel"]
    assert sorts and all(scope[id(o)] == "group_sort" for o in sorts)
    assert {scope[id(o)] for o in kernels} == {"segment_agg_sorted",
                                               "segment_agg_unsorted"}
    assert {"sort_gather", "join.probe", "keyslot.probe"} <= set(t.scopes)
    gather = spans.gather_ms(t, 4)
    want = sum(e - s for s, e, o in trace_reduce.own_time(t.trace.ops)
               if scope[id(o)] == "sort_gather") * 1e-6 / 4
    assert gather == pytest.approx(want) and gather > 0


def test_recorded_dispatch_idle_is_part_of_the_idle(recorded):
    _path, t = recorded
    dispatch = spans.idle_pct_dispatch(t)
    idle = load_module("metrics", "idle_pct.scan").read(
        SimpleNamespace(trace=t.trace))
    assert 0 < dispatch <= idle
    scan = spans.guard_scan_ms(t)
    assert 0 < scan < 10


def test_span_report_on_the_recorded_trace(recorded, monkeypatch, capsys):
    path, t = recorded
    tool = os.path.join(os.path.dirname(DATA), os.pardir, "tools",
                        "span_report.py")
    spec = importlib.util.spec_from_file_location("span_report", tool)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    monkeypatch.setattr(sys, "argv", ["span_report.py", path,
                                      "--requests", "4",
                                      "--min-gap-ms", "0"])
    assert mod.main() == 0
    out = json.loads(capsys.readouterr().out)
    assert out["gather_ms"] == pytest.approx(spans.gather_ms(t, 4))
    assert out["guard_scan_ms"] == pytest.approx(spans.guard_scan_ms(t))
    assert out["scope_ms"]["sort_gather"] == pytest.approx(
        out["gather_ms"] * 4)
    assert out["idle_gaps"] and all(
        label == "no span open" or label.startswith("agg.")
        for label, _s in out["idle_gaps"])
