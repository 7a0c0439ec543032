"""Production serving launcher.

Two workloads share this entry point:

* ``--workload agg`` (default) — the aggregate-serving layer
  (``repro.serve.agg_server``): a synthetic dashboard of parameterized
  grouped-aggregate tiles is served through the compiled-plan +
  slot-table caches with same-shape request batching, and the launcher
  reports sustained throughput, latency quantiles, and the cache
  counters (traces / slot builds) that show the per-request work
  amortized away.

      PYTHONPATH=src python -m repro.launch.serve --rows 50000 --requests 1000

* ``--workload lm`` — the continuous-batching LM server
  (``repro.serve.serving``) over a selected arch.  ``--smoke`` serves
  the reduced config locally.

      PYTHONPATH=src python -m repro.launch.serve --workload lm --arch qwen3-14b --smoke
"""
from __future__ import annotations

import argparse
import time


def _serve_lm(args) -> None:
    import jax
    import numpy as np

    from repro.configs import get_config
    from repro.models import LM
    from repro.serve.serving import Request, Server

    cfg = get_config(args.arch)
    if args.smoke:
        cfg = cfg.reduced()
    if cfg.family in ("vlm", "audio"):
        raise SystemExit("text-only serving driver")
    lm = LM(cfg, q_chunk=32 if args.smoke else 1024,
            kv_chunk=32 if args.smoke else 1024,
            ssd_chunk=8 if args.smoke else 128)
    params = lm.init(jax.random.PRNGKey(0))
    server = Server(lm, params, slots=args.slots, max_len=args.max_len)

    rng = np.random.default_rng(0)
    reqs = [Request(rid=i,
                    prompt=rng.integers(0, cfg.vocab,
                                        rng.integers(3, 12)).tolist(),
                    max_new=args.max_new)
            for i in range(args.requests)]
    for r in reqs:
        server.submit(r)
    t0 = time.perf_counter()
    server.run()
    dt = time.perf_counter() - t0
    toks = sum(len(r.out) for r in reqs)
    print(f"{sum(r.done for r in reqs)}/{len(reqs)} requests, "
          f"{toks} tokens, {toks/dt:.1f} tok/s")


def _serve_agg(args) -> None:
    import numpy as np

    from repro.relational import Table
    from repro.relational.plan import GroupAgg, Scan
    from repro.serve import AggServer, serving_enabled

    rng = np.random.default_rng(0)
    n, groups = args.rows, args.groups
    t = Table.from_columns(
        k=rng.integers(0, groups, n).astype(np.int32),
        v=rng.integers(-4, 5, n).astype(np.float32),
        w=rng.integers(0, 100, n).astype(np.float32))
    # two dashboard tiles over one fact table — no declared bound: the
    # server's distinct-count sketch infers max_groups and validates it
    tiles = [
        GroupAgg(Scan("T", ("k", "v", "w")), ("k",),
                 (("rev", "sum", "v"), ("n", "count", None),
                  ("hi", "max", "v"))),
        GroupAgg(Scan("T", ("k", "v", "w")), ("k",),
                 (("avg_w", "mean", "w"), ("lo", "min", "v"))),
    ]
    srv = AggServer({"T": t}, max_batch=args.max_batch)
    for tile in tiles:
        srv.execute(tile, {})               # warm: trace + slot build
        print("tile:", srv.describe(tile))

    lat: list = []
    t0 = time.perf_counter()
    futs = []
    for i in range(args.requests):
        ts = time.perf_counter()
        f = srv.submit(tiles[i % len(tiles)], {})
        f.add_done_callback(
            lambda _f, ts=ts: lat.append(time.perf_counter() - ts))
        futs.append(f)
    for f in futs:
        f.result(timeout=300)
    dt = time.perf_counter() - t0
    srv.close()
    q = np.quantile(np.asarray(lat), [0.5, 0.99]) * 1e3
    mode = "cached" if serving_enabled() else "kill-switch (REPRO_AGG_SERVE=off)"
    print(f"{args.requests} requests in {dt:.3f}s — "
          f"{args.requests/dt:.0f} qps, p50 {q[0]:.2f} ms, p99 {q[1]:.2f} ms "
          f"[{mode}]")
    print(f"traces={srv.stats.traces} slot_builds={srv.stats.slot_builds} "
          f"batches={srv.stats.batches}")


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--workload", choices=("agg", "lm"), default="agg")
    # agg workload
    ap.add_argument("--rows", type=int, default=50_000)
    ap.add_argument("--groups", type=int, default=500)
    ap.add_argument("--max-batch", type=int, default=64)
    # lm workload
    ap.add_argument("--arch", default="qwen3-14b")
    ap.add_argument("--smoke", action="store_true")
    ap.add_argument("--slots", type=int, default=4)
    ap.add_argument("--max-new", type=int, default=16)
    ap.add_argument("--max-len", type=int, default=128)
    # shared (the LM smoke default was 8; agg streams default to 1000)
    ap.add_argument("--requests", type=int, default=None)
    args = ap.parse_args()
    if args.requests is None:
        args.requests = 8 if args.workload == "lm" else 1000
    from repro.launch.compile_cache import configure_compile_cache
    configure_compile_cache()
    if args.workload == "lm":
        _serve_lm(args)
    else:
        _serve_agg(args)


if __name__ == "__main__":
    main()
