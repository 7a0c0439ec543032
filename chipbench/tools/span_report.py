"""Read the program's spans and scopes out of one profiler trace.

Prints, as one JSON object, what ``chipbench.spans`` reads from a trace
that the JAX profiler wrote around a running ``AggServer`` (as
``docs/serving.md`` shows, or ``chipbench/tools/record_trace.py``):
``guard_scan_ms``, ``idle_pct.dispatch``, ``gather_ms`` per request when
``--requests`` gives the number completed in the window, the device time
of each named scope, and the device's idle gaps of at least
``--min-gap-ms`` with the ``agg.*`` span that was open when each began.

    python chipbench/tools/span_report.py <trace dir or .xplane.pb> [--requests N]
"""
from __future__ import annotations

import argparse
import json
import os
import sys

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("trace")
    ap.add_argument("--requests", type=int, default=0)
    ap.add_argument("--min-gap-ms", type=float, default=100.0)
    args = ap.parse_args()
    sys.path[:0] = [os.path.join(ROOT, "src"), ROOT]

    from chipbench import spans, trace_reduce
    path = args.trace if os.path.isfile(args.trace) \
        else trace_reduce.find_xplane(args.trace)
    t = spans.reduce(path)
    lo, hi = t.trace.window
    scope_ms: dict = {}
    by_op = {id(o): sc for o, sc in zip(t.trace.ops, t.scopes)}
    for s, e, o in trace_reduce.own_time(t.trace.ops):
        k = by_op[id(o)] or "(no scope)"
        scope_ms[k] = scope_ms.get(k, 0.0) + max(
            0, min(e, hi) - max(s, lo)) * 1e-6
    gaps = [g for g in t.trace.idle_gaps(
                lambda s, e: spans.open_span(t, s, e), 1 << 30)
            if g[1] * 1e3 >= args.min_gap_ms]
    print(json.dumps({
        "window_s": t.trace.window_s,
        "busy_s": t.trace.busy_s(),
        "guard_scan_ms": spans.guard_scan_ms(t),
        "idle_pct.dispatch": spans.idle_pct_dispatch(t),
        "gather_ms": spans.gather_ms(t, args.requests),
        "scope_ms": dict(sorted(scope_ms.items(), key=lambda kv: -kv[1])),
        "idle_gaps": gaps,
    }, indent=1))
    return 0


if __name__ == "__main__":
    sys.exit(main())
