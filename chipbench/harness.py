"""One run of one benchmark cell: set-up, the measured window, the
metrics, and the comparison that decides ``correct``.

Everything that belongs to a configuration, a traffic mix or a metric is
found by name:

* the cell: its entry in ``BENCHMARK.json`` (configuration, traffic,
  chips);
* the configuration: the file ``BENCHMARK.json`` names for it, which in
  turn names its data generator (``chipbench/data/<data>.py``), its plans
  (``chipbench/data/<plans>.py``) and its plain reference
  (``chipbench/reference/<reference>.py``);
* the traffic: ``chipbench/traffic/<traffic>.json``, read by
  ``chipbench/loadgen.py``;
* each metric: ``chipbench/metrics/<name>.py``, a ``read(run)`` that
  returns a number, or None when the run holds nothing to read.
"""
from __future__ import annotations

import copy
import importlib.util
import json
import os
import shutil
import sys
import tempfile
import time
from dataclasses import dataclass
from typing import Any, Optional

import numpy as np

from chipbench import loadgen

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(HERE)

#: how long a run waits for the answers still due after the window
DRAIN_S = 120.0
#: the server's guard counters a run must leave at 0: a launch of the
#: primary executable that failed, a batch served by the jnp fallback
#: instead, a circuit breaker that opened.  Any of them means answers came
#: from another path than the one measured.
GUARD_COUNTERS = ("backend_failures", "degraded_launches", "breaker_trips")


def load_json(path: str) -> dict:
    with open(path) as f:
        return json.load(f)


def load_module(kind: str, name: str):
    """``chipbench/<kind>/<name>.py`` as a module, by file path (names may
    hold dots)."""
    path = os.path.join(HERE, kind, f"{name}.py")
    if not os.path.isfile(path):
        raise FileNotFoundError(f"no {kind} module {name!r} at {path}")
    mod_name = f"chipbench._{kind}_{name.replace('.', '_')}"
    if mod_name in sys.modules:
        return sys.modules[mod_name]
    spec = importlib.util.spec_from_file_location(mod_name, path)
    mod = importlib.util.module_from_spec(spec)
    sys.modules[mod_name] = mod
    spec.loader.exec_module(mod)
    return mod


@dataclass
class Cell:
    """A workload of ``BENCHMARK.json`` with everything it names."""
    name: str
    chips: int
    config: dict
    traffic: dict
    end_to_end: list
    per_layer: list

    @staticmethod
    def load(bench: dict, name: str, root: str = ROOT) -> "Cell":
        wl = {w["name"]: w for w in bench["workloads"]}
        if name not in wl:
            raise KeyError(f"no workload {name!r} in BENCHMARK.json")
        w = wl[name]
        cfg_entry = {c["name"]: c for c in bench["configs"]}[w["config"]]
        config = load_json(os.path.join(root, cfg_entry["file"]))
        traffic = load_json(os.path.join(HERE, "traffic",
                                         f"{w['traffic']}.json"))

        def mine(metrics):
            return [m for m in metrics
                    if "workloads" not in m or name in m["workloads"]]
        return Cell(name, int(w["chips"]), config, traffic,
                    mine(bench["end_to_end"]), mine(bench["per_layer"]))


@dataclass
class Run:
    """What the metric readers read."""
    cell: Cell
    records: list
    t0: float
    close_at: float
    setup_s: float
    rows: dict                  # record id → input rows
    launches: list              # [(plan, [records])] coalesced launches
    bytes_per_launch: dict      # plan → input bytes of one launch
    bytes_per_result: dict      # plan → bytes of one result
    stats0: Any = None
    stats1: Any = None
    trace: Any = None           # trace_reduce.Trace of the window
    device_kind: str = ""

    @property
    def window_s(self) -> float:
        return self.close_at - self.t0

    def requests(self) -> list:
        """Requests answered inside the window."""
        return [r for r in self.records
                if r.error is None and r.done <= self.close_at]


def launches_of(records) -> list:
    """Requests coalesced into one launch finish together: group the
    successful requests of each plan whose answers arrived within a
    millisecond of each other."""
    by_plan: dict = {}
    for r in records:
        by_plan.setdefault(r.plan, []).append(r)
    out = []
    for plan, rs in by_plan.items():
        rs.sort(key=lambda r: r.done)
        group = [rs[0]]
        for r in rs[1:]:
            if r.done - group[-1].done <= 1e-3:
                group.append(r)
            else:
                out.append((plan, group))
                group = [r]
        out.append((plan, group))
    return out


def _cast_params(base: dict, override: dict) -> dict:
    out = dict(base)
    for k, v in override.items():
        dt = np.asarray(base[k]).dtype if k in base else np.asarray(v).dtype
        out[k] = np.asarray(v, dtype=dt)
    return out


class Counter:
    """Counts backend compiles (JAX's monitoring events) while armed."""

    EVENT = "/jax/core/compile/backend_compile_duration"

    def __init__(self):
        self.n = 0
        self.armed = False

    def __call__(self, event, duration, **kw):
        if self.armed and event == self.EVENT:
            self.n += 1


def run(bench: dict, cell_name: str, seed: int, seconds: float,
        trace: bool, *, t_start: Optional[float] = None,
        config_override: Optional[dict] = None, root: str = ROOT,
        log=None) -> dict:
    """One run of one cell on the devices JAX holds; returns the result
    line's object, with ``checks`` last.  ``config_override`` replaces
    keys of the cell's configuration (tests at a small scale)."""
    import jax
    from repro.serve import AggServer, ServeRequest

    log = log or (lambda *a: print(*a, file=sys.stderr, flush=True))
    t_start = time.perf_counter() if t_start is None else t_start
    cell = Cell.load(bench, cell_name, root)
    cfg = dict(cell.config)
    cfg.update(config_override or {})
    data = load_module("data", cfg["data"])
    plans_mod = load_module("data", cfg["plans"])
    ref_mod = load_module("reference", cfg["reference"])
    devices = jax.devices()
    dev = devices[0]

    streams = cell.traffic["streams"]
    names = sorted({r["plan"] for s in streams
                    for r in (s.get("cycle") or s.get("mix"))})
    n = data.sizes(cfg["scale"])

    # -- set-up: data, server, warm-up -------------------------------------
    catalog = data.generate(cfg["scale"], seed)
    jax.block_until_ready([t.columns for t in catalog.values()])
    log(f"setup: data at scale {cfg['scale']} "
        f"({time.perf_counter() - t_start:.1f}s since start)")
    srv = AggServer(catalog, **cfg.get("server", {}))
    plans = plans_mod.build(catalog, names)
    bases = {k: p for k, (_plan, p) in plans.items()}
    del catalog
    rng = data.host_rng(seed, 1)
    sched = [(s, loadgen.client_sequences(s, rng)) for s in streams]
    _warm_up(srv, plans, sched, cfg, ServeRequest)
    log(f"setup: warm ({time.perf_counter() - t_start:.1f}s since start)")

    def send(req):
        plan, base = plans[req.plan]
        params = _cast_params(base, req.params)
        return srv.serve_async(ServeRequest(
            plan, params or None, consistency=req.consistency)).result()

    def consume(req, res):
        got = res.table.to_numpy()
        return {c: got[c] for c in plans_mod.result_columns(req.plan)}

    counter = Counter()
    jax.monitoring.register_event_duration_secs_listener(counter)
    tdir = tempfile.mkdtemp(prefix="chipbench_trace_") if trace else None
    state: dict = {}

    def on_open(win):
        state["stats0"] = copy.copy(srv.stats)
        counter.armed = True
        if trace:
            opts = jax.profiler.ProfileOptions()
            opts.python_tracer_level = 0
            opts.host_tracer_level = 1
            jax.profiler.start_trace(tdir, profiler_options=opts)
            from chipbench.trace_reduce import WINDOW_SPAN
            state["ann"] = jax.profiler.TraceAnnotation(WINDOW_SPAN)
            state["ann"].__enter__()
        state["setup_s"] = time.perf_counter() - t_start

    # -- the window ---------------------------------------------------------
    win, threads = loadgen.run_window(sched, seconds, send, consume,
                                      on_open)
    stats1 = copy.copy(srv.stats)
    if trace:
        state["ann"].__exit__(None, None, None)
        jax.profiler.stop_trace()
    counter.armed = False
    jax.monitoring.unregister_event_duration_listener(counter)
    deadline = time.perf_counter() + DRAIN_S
    for th in threads:
        th.join(timeout=max(0.1, deadline - time.perf_counter()))
    stuck = [th.name for th in threads if th.is_alive()]
    guard = {f"guard.{k}": float(getattr(srv.guard_stats, k))
             for k in GUARD_COUNTERS}
    window_compiles = counter.n
    peak = max((d.memory_stats() or {}).get("peak_bytes_in_use", 0)
               for d in devices)
    log(f"window: {win.close_at - win.t0:.3f}s, {len(win.records)} "
        f"requests, {window_compiles} compiles inside, "
        f"traces {stats1.traces - state['stats0'].traces}, "
        f"stuck {stuck}")

    # -- release the program's state, then compare ------------------------
    host = _host_copy(srv, plans_mod, names, cfg)
    srv.close(drain=False)
    del srv, plans
    records = list(win.records)
    rows = {id(r): sum(n[t] for t in plans_mod.scans(r.plan))
            for r in records}
    ref = ref_mod.Reference(host, plans_mod.result_columns)
    checks, compared = _compare(records, ref, bases, cfg["limits"])
    checks.update({k: {"value": v, "limit": 0} for k, v in guard.items()})
    failed = sum(1 for r in records if r.error is not None) + len(stuck)
    for r in records:
        if r.error is not None:
            log(f"failed: {r.plan} {r.error}")

    # -- metrics -------------------------------------------------------------
    launches = launches_of([r for r in records if r.error is None
                            and r.done <= win.close_at])
    bpl = {p: _launch_bytes(plans_mod, p, n) for p in names}
    bpr = {p: _result_bytes(plans_mod, p, n) for p in names}
    tr = None
    if trace:
        from chipbench import trace_reduce
        tr = trace_reduce.reduce(trace_reduce.find_xplane(tdir))
        shutil.rmtree(tdir, ignore_errors=True)
    runv = Run(cell, records, win.t0, win.close_at, state["setup_s"], rows,
               launches, bpl, bpr, state["stats0"], stats1, tr,
               dev.device_kind)
    wanted = cell.per_layer if trace else cell.end_to_end
    metrics = {}
    for m in wanted:
        v = load_module("metrics", m["name"]).read(runv)
        if v is not None:
            metrics[m["name"]] = {"value": float(v), "unit": m["unit"]}
    device = {"platform": dev.platform, "kind": dev.device_kind,
              "count": len(devices), "memory_peak_bytes": int(peak)}
    out = {"correct": bool(failed == 0 and compared > 0 and all(
               c["value"] <= c["limit"] for c in checks.values())),
           "attempted": len(records), "failed": failed,
           "metrics": metrics, "device": device}
    if tr is not None:
        device["busy_s"] = tr.busy_s()
        device["window_s"] = tr.window_s
        out["breakdown"] = {"device_ops": tr.top_ops(10),
                            "idle_gaps": tr.idle_gaps(
                                _gap_label(tr, records, win.t0), 10)}
    log(f"compared {compared} answers; window_compiles {window_compiles}")
    out["checks"] = checks
    return out


def _warm_up(srv, plans, sched, cfg, ServeRequest):
    """Run every shape the window will run: each plan at every batch
    bucket its clients can fill, and each read at another consistency
    than ``latest``."""
    max_batch = int(cfg.get("server", {}).get("max_batch", 64))
    latest: dict = {}
    other = set()
    for _s, seqs in sched:
        for seq in seqs:
            for plan, cons in {(r.plan, r.consistency) for r in seq}:
                if cons == "latest":
                    latest[plan] = latest.get(plan, 0) + 1
                else:
                    other.add((plan, cons))
    for name, clients in sorted(latest.items()):
        plan, params = plans[name]
        top = min(clients, max_batch)
        sizes = tuple(1 << i for i in range(top.bit_length())
                      if 1 << i <= top) if params else (1,)
        if params and top & (top - 1):
            sizes += (1 << top.bit_length(),)
        srv.warmup(plan, params, batch_sizes=sizes)
    for name, cons in sorted(other):
        plan, params = plans[name]
        srv.serve(ServeRequest(plan, params or None, consistency=cons))


def _host_copy(srv, plans_mod, names, cfg) -> dict:
    """Host copies of the columns the references read."""
    need: dict = {}
    for name in names:
        for t, cols in plans_mod.reads(name).items():
            need.setdefault(t, set()).update(cols)
    for t, cols in cfg.get("reference_reads", {}).items():
        need.setdefault(t, set()).update(cols)
    out = {}
    for t, cols in need.items():
        tab = srv.table(t)
        out[t] = {c: np.asarray(tab.columns[c]) for c in sorted(cols)}
    return out


def _compare(records, ref, bases, limits):
    """Every answer of the window against the plain reference; returns
    ({number: {value, limit}}, answers compared)."""
    worst: dict = {}
    compared = 0
    for r in records:
        if r.error is not None:
            continue
        params = {**bases[r.plan], **r.params}
        for num, v in ref.check(r.plan, params, r.got).items():
            key = f"{r.plan}.{num}"
            worst[key] = max(worst.get(key, 0.0), float(v))
        compared += 1
    checks = {}
    for key in sorted(worst):
        if key not in limits:
            raise KeyError(f"no limit for {key!r} in the configuration")
        checks[key] = {"value": worst[key], "limit": limits[key]}
    return checks, compared


def _launch_bytes(plans_mod, name, n) -> int:
    from chipbench.roofline import input_bytes
    return input_bytes(plans_mod.reads(name), n)


def _result_bytes(plans_mod, name, n) -> int:
    from chipbench.roofline import output_bytes
    return output_bytes(plans_mod.groups(name, n),
                        len(plans_mod.result_columns(name)))


def _gap_label(tr, records, t0):
    """Names an idle gap of the device by the requests in flight."""
    base = tr.window[0]

    def to_ns(t):
        return base + int((t - t0) * 1e9)
    live = [(to_ns(r.start), to_ns(r.done), r.plan) for r in records
            if r.done is not None]

    def label(s, e):
        plans = sorted({p for a, b, p in live if a < e and b > s})
        return ("in flight: " + ",".join(plans)) if plans \
            else "no request in flight"
    return label
