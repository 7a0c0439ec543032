"""Run one benchmark cell once and print its result line.

    python3 chipbench/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

From the root of a checkout.  The cell, its configuration, traffic and
metrics are found by name from ``BENCHMARK.json`` (see
``chipbench/README.md``).  The run makes its data from ``--seed``, warms
every shape its traffic uses, measures for ``--seconds``, checks every
answer of the window against the plain reference, prints each number
compared beside its limit on standard error, and prints one JSON object
as the last line of standard output.  It exits non-zero, with no result
line, when JAX finds no TPU or fewer chips than the cell asks for, or when
the system under test (``src/``) is not in the checkout.
"""
from __future__ import annotations

import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import sys  # noqa: E402

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)

    sys.path[:0] = [ROOT, os.path.join(ROOT, "src")]
    from chipbench import harness
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        bench = json.load(f)
    cell = harness.Cell.load(bench, args.workload, ROOT)
    try:
        import repro.serve  # noqa: F401
    except ImportError as e:
        print(f"chipbench: the system under test is not in this checkout "
              f"({e})", file=sys.stderr)
        return 2

    import jax
    devices = jax.devices()
    if devices[0].platform != "tpu":
        print(f"chipbench: needs a TPU; JAX found {devices[0].platform}",
              file=sys.stderr)
        return 3
    if len(devices) < cell.chips:
        print(f"chipbench: {args.workload} needs {cell.chips} chips; JAX "
              f"found {len(devices)}", file=sys.stderr)
        return 3
    from repro.launch.compile_cache import configure_compile_cache
    print(f"chipbench: {devices[0].device_kind} x{len(devices)}, compile "
          f"cache {configure_compile_cache()}", file=sys.stderr, flush=True)

    out = harness.run(bench, args.workload, args.seed, args.seconds,
                      bool(args.trace), t_start=T_START)
    print(f"correct: {out['correct']}", file=sys.stderr)
    for name, c in out["checks"].items():
        print(f"check {name}: {c['value']!r} (limit {c['limit']!r})",
              file=sys.stderr)
    sys.stderr.flush()
    print(json.dumps(out), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
