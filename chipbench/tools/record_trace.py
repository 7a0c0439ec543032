"""Record a small profiler trace of the served path, and describe it.

Serves the four TPC-H loops of ``loops.closed4`` (Q18 and Q21 over
LINEITEM, Q13 over ORDERS, Q2 over PARTSUPP joined with SUPPLIER) through
``AggServer`` at a small scale, once each to compile and once more under
the profiler inside the benchmark's ``chipbench.window`` span, then
prints each plane, line, op name and stat key of the trace and the
reduction ``chipbench.trace_reduce`` makes of it.  The trace file is
copied to ``--out`` (the test fixture ``chipbench/tests/data`` is one such
recording, made on one TPU v5e).

    python chipbench/tools/record_trace.py --out <dir>
"""
from __future__ import annotations

import argparse
import os
import shutil
import sys
import tempfile
from collections import Counter

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--out", required=True)
    ap.add_argument("--scale", type=float, default=0.02)
    args = ap.parse_args()
    sys.path[:0] = [os.path.join(ROOT, "src"), ROOT]

    import jax
    from chipbench import trace_reduce
    from chipbench.data import loops, tpch
    from repro.serve import AggServer

    cat = tpch.generate(args.scale, 7)
    jax.block_until_ready([t.columns for t in cat.values()])
    srv = AggServer(cat, max_batch=1)
    reqs = list(loops.build(cat, ["Q18", "Q2", "Q21", "Q13"]).values())
    for plan, params in reqs:                       # compile outside
        srv.execute(plan, params)
    tmp = tempfile.mkdtemp(prefix="chipbench_trace_")
    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    opts.host_tracer_level = 1
    jax.profiler.start_trace(tmp, profiler_options=opts)
    with jax.profiler.TraceAnnotation(trace_reduce.WINDOW_SPAN):
        for plan, params in reqs:
            srv.execute(plan, params)
    jax.profiler.stop_trace()
    srv.close()

    path = trace_reduce.find_xplane(tmp)
    os.makedirs(args.out, exist_ok=True)
    shutil.copy(path, os.path.join(args.out, "small.xplane.pb"))
    print(f"trace: {os.path.getsize(path)} bytes", flush=True)
    shutil.rmtree(tmp, ignore_errors=True)

    from jax.profiler import ProfileData
    pd = ProfileData.from_file(os.path.join(args.out, "small.xplane.pb"))
    for plane in pd.planes:
        lines = list(plane.lines)
        print(f"PLANE {plane.name!r} lines={len(lines)}")
        for line in lines:
            evs = list(line.events)
            print(f"  LINE {line.name!r} events={len(evs)}")
            if not plane.name.startswith("/device:"):
                names = Counter(e.name for e in evs)
                print("    top:", names.most_common(8))
                continue
            keys, cats, names = Counter(), Counter(), Counter()
            for e in evs:
                st = dict(e.stats)
                keys.update(st.keys())
                cats[str(st.get("hlo_category"))] += 1
                names[e.name] += 1
            print("    stat keys:", dict(keys))
            print("    hlo_category:", dict(cats))
            print("    names:", names.most_common(40))
            for e in evs[:3]:
                print("    e.g.", e.name, e.start_ns, e.duration_ns,
                      dict(e.stats))
    tr = trace_reduce.reduce(os.path.join(args.out, "small.xplane.pb"))
    print("reduced: window_s", tr.window_s, "busy_s", tr.busy_s(),
          "devices", tr.devices, "sort_s", tr.category_s("sort"),
          "kernel_s", tr.category_s("kernel"), "other_s",
          tr.category_s("other"))
    print("top ops", tr.top_ops())
    return 0


if __name__ == "__main__":
    sys.exit(main())
