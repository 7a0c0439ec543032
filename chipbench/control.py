"""The control: the plain reference put in the program's place, computed
in bfloat16, the precision below the float32 the configurations state.

For each cell it computes every kind of answer the cell's traffic asks
for (each plan at each parameter the traffic can send) with the
reference's ``control`` path, and compares it with the reference as a
run compares the program's answers.  Each reading is printed beside the configuration's limit; the
control must fail at least one number of every cell.

    python3 chipbench/control.py --workload <cell> --seeds 11 12 13

It makes the data at the configuration's scale on the default device and
computes on the host; ``chipbench/tests/test_chipbench_control.py`` runs
it at a small scale on the CPU.
"""
from __future__ import annotations

import argparse
import json
import os
import sys

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def kinds(cell) -> list:
    """(plan, traffic parameters) of every answer the cell's traffic can
    ask for; an integer range contributes its ends and middle."""
    out = []
    for s in cell.traffic["streams"]:
        for r in s.get("cycle") or s.get("mix"):
            params = {}
            for k, v in r.get("params", {}).items():
                if isinstance(v, dict) and "int_uniform" in v:
                    lo, hi = v["int_uniform"]
                    params[k] = [lo, (lo + hi) // 2, hi]
                else:
                    params[k] = [v]
            combos = [{}]
            for k, vs in params.items():
                combos = [{**c, k: v} for c in combos for v in vs]
            for c in combos:
                if (r["plan"], c) not in out:
                    out.append((r["plan"], c))
    return out


def readings(bench: dict, cell_name: str, seed: int, *,
             config_override=None, control: bool = True) -> dict:
    """{number: worst value} of the control (or, with ``control=False``,
    of the reference against itself) over every kind of answer."""
    import jax
    import numpy as np
    from chipbench import harness

    cell = harness.Cell.load(bench, cell_name)
    cfg = {**cell.config, **(config_override or {})}
    data = harness.load_module("data", cfg["data"])
    plans_mod = harness.load_module("data", cfg["plans"])
    ref_mod = harness.load_module("reference", cfg["reference"])
    catalog = data.generate(cfg["scale"], seed)
    todo = kinds(cell)
    bases = {k: p for k, (_pl, p) in
             plans_mod.build(catalog, sorted({k for k, _ in todo})).items()}
    need: dict = {}
    for name, _ in todo:
        for t, cols in plans_mod.reads(name).items():
            need.setdefault(t, set()).update(cols)
    for t, cols in cfg.get("reference_reads", {}).items():
        need.setdefault(t, set()).update(cols)
    host = {t: {c: np.asarray(catalog[t].columns[c]) for c in sorted(cols)}
            for t, cols in need.items()}
    del catalog
    jax.clear_caches()
    ref = ref_mod.Reference(host, plans_mod.result_columns)
    worst: dict = {}
    for name, p in todo:
        params = harness._cast_params(bases[name], p)
        got = ref.answer(name, params, control=control)
        for num, v in ref.check(name, params, got).items():
            key = f"{name}.{num}"
            worst[key] = max(worst.get(key, 0.0), float(v))
    return worst


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", type=int, nargs="+", required=True)
    args = ap.parse_args(argv)
    sys.path[:0] = [ROOT, os.path.join(ROOT, "src")]
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        bench = json.load(f)
    from chipbench import harness
    limits = harness.Cell.load(bench, args.workload).config["limits"]
    for seed in args.seeds:
        got = readings(bench, args.workload, seed)
        failed = [k for k, v in got.items() if v > limits[k]]
        print(json.dumps({"workload": args.workload, "seed": seed,
                          "readings": got, "fails": failed}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
