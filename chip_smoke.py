#!/usr/bin/env python3
"""Smoke run of the Aggify serving path on a TPU, at TPC-H SF 10.

One process, through the entry points a user calls:

* ``gen_tpch(scale=10)`` makes the catalog from ``--seed`` (60M LINEITEM,
  15M ORDERS, 8M PARTSUPP rows) and puts it on the device;
* the paper's TPC-H cursor loops (``benchmarks/queries.py``) are
  aggified; the grouped (decorrelated, "Aggify+") calls Q2, Q13, Q18 and
  Q21 are served by ``AggServer`` (``execute`` and ``submit``) with their
  correlation domain declared as ``max_groups``, beside three ``GroupAgg``
  tiles; Q14 and Q19 run through ``run_rewritten``;
* micro-batches are ``ingest``-ed into a resident tile and read back with
  an epoch read;
* every result is checked against plain numpy group-bys over all groups,
  and against ``run_cursor`` (the sequential cursor semantics) for a few
  sampled correlation keys.

It fails, exiting non-zero without a result line, when the device is not
a TPU, when a served executable meant for the kernel holds no
``tpu_custom_call``, when the serving guard absorbed any backend failure,
or when any result disagrees.  The last line of a passing run is
``{"ok": true, "device": {"platform", "kind", "count"}}``.

    python chip_smoke.py              # one chip
    python chip_smoke.py --chips 4    # LINEITEM row-sharded over 4 chips:
                                      # only the sharded path and the
                                      # one-device runs it must equal
"""
from __future__ import annotations

import argparse
import json
import os
import sys
import time

import numpy as np

ROOT = os.path.dirname(os.path.abspath(__file__))

#: correlation domain of each grouped loop: the table whose key the
#: loop is invoked per (the Aggify+ group count)
DOMAIN = {"Q2": "PART", "Q13": "CUSTOMER", "Q18": "ORDERS",
          "Q21": "SUPPLIER"}

#: capacity of the per-key tables the cursor reference walks
CURSOR_ROWS = {"Q2": 64, "Q13": 256, "Q18": 64, "Q21": 2048}

#: ingest micro-batches folded into the resident tile
INGEST_BATCHES, INGEST_ROWS = 3, 4096


class Checks:
    """Collects failed checks; every check prints one line."""

    def __init__(self):
        self.failed: list[str] = []

    def __call__(self, ok: bool, what: str) -> bool:
        print(f"  {'ok  ' if ok else 'FAIL'} {what}", flush=True)
        if not ok:
            self.failed.append(what)
        return ok


def _import_repo():
    sys.path[:0] = [os.path.join(ROOT, "src"), ROOT]
    try:
        import repro  # noqa: F401
        import benchmarks.queries  # noqa: F401
    except ImportError as e:
        sys.exit(f"chip_smoke: run from the repository checkout ({e})")


def _host(catalog) -> dict:
    """Host copies of every column — the numpy references' input."""
    return {name: {c: np.asarray(a) for c, a in t.columns.items()}
            for name, t in catalog.items()}


def _result_map(table, key: str, col: str) -> tuple[np.ndarray, np.ndarray]:
    got = table.to_numpy()
    return np.asarray(got[key]), np.asarray(got[col])


def _check_groups(check, name, keys, vals, present, ref, exact=True):
    """All groups present in the data, each exactly once, each value
    equal to the numpy reference."""
    want = np.flatnonzero(present)
    check(len(keys) == len(np.unique(keys)) and len(keys) == len(want)
          and np.array_equal(np.sort(keys), want),
          f"{name}: {len(keys)} groups, numpy has {len(want)}")
    if len(keys) != len(want) or not np.isin(keys, want).all():
        return
    r = ref[keys]
    ok = (np.array_equal(vals, r) if exact
          else np.allclose(vals, r, rtol=1e-6, atol=1e-6))
    bad = int(np.sum(vals != r)) if exact else int(
        np.sum(~np.isclose(vals, r, rtol=1e-6, atol=1e-6)))
    check(ok, f"{name}: values match numpy over all groups "
              f"({bad} differ)")


# -- plain numpy references ------------------------------------------------


def numpy_grouped(q: str, h: dict, params: dict):
    """(present[key], value[key]) for one grouped loop, over all groups."""
    if q == "Q13":
        o = h["ORDERS"]
        k = o["o_custkey"]
        n = len(h["CUSTOMER"]["c_custkey"])
        return (np.bincount(k, minlength=n) > 0,
                np.bincount(k, weights=~o["o_comment_special"], minlength=n))
    li = h["LINEITEM"]
    if q == "Q18":
        k, n = li["l_orderkey"], len(h["ORDERS"]["o_orderkey"])
        return (np.bincount(k, minlength=n) > 0,
                np.bincount(k, weights=li["l_quantity"], minlength=n))
    if q == "Q21":
        k, n = li["l_suppkey"], len(h["SUPPLIER"]["s_suppkey"])
        late = li["l_receiptdate"] > li["l_commitdate"]
        return (np.bincount(k, minlength=n) > 0,
                np.bincount(k, weights=late, minlength=n))
    # Q2: per part, the first row of least supplycost among those above
    # the lower bound (and below the loop's initial minCost); its
    # supplier's name, -1 when none qualifies
    ps, s = h["PARTSUPP"], h["SUPPLIER"]
    part, cost = ps["ps_partkey"], ps["ps_supplycost"]
    n = len(h["PART"]["p_partkey"])
    idx = np.flatnonzero((cost > params["lb"]) & (cost < params["minCost"]))
    order = idx[np.lexsort((idx, cost[idx], part[idx]))]
    first_part, first = np.unique(part[order], return_index=True)
    name = np.full(n, -1, np.int64)
    name[first_part] = s["s_name"][ps["ps_suppkey"][order[first]]]
    return np.bincount(part, minlength=n) > 0, name


def numpy_tile(h: dict, table: str, key: str, aggs) -> tuple:
    t = h[table]
    k = t[key]
    m = t.get("__valid__", np.ones(len(k), bool))
    k = k[m]
    n = int(k.max()) + 1
    present = np.bincount(k, minlength=n) > 0
    out = {}
    for name, op, col in aggs:
        v = None if col is None else t[col][m].astype(np.float64)
        if op == "count":
            out[name] = np.bincount(k, minlength=n)
        elif op == "sum":
            out[name] = np.bincount(k, weights=v, minlength=n)
        elif op == "mean":
            c = np.bincount(k, minlength=n)
            out[name] = np.bincount(k, weights=v, minlength=n) / np.maximum(
                c, 1)
        else:
            r = np.full(n, np.inf if op == "min" else -np.inf)
            (np.minimum if op == "min" else np.maximum).at(r, k, v)
            out[name] = r
    return present, out


# -- the served loops --------------------------------------------------------


def grouped_calls(catalog):
    """Aggify+ form of each correlated loop: the correlation filter
    stripped, the loop grouped by the correlation column, the group count
    bounded by the correlation domain.  Returns name → (call, program,
    params): ``params`` are the loop's scalar parameters plus the
    pre-loop state of the aggregate's fields."""
    import jax.numpy as jnp
    from benchmarks.queries import DEFAULT_PARAMS, QUERIES
    from repro.core import aggify
    from repro.core.executors import build_env
    from repro.relational.plan import AggCall, Filter

    out = {}
    for q in ("Q2", "Q13", "Q18", "Q21"):
        factory, corr, gk = QUERIES[q]
        prog = factory()
        rp = aggify(prog)
        child = rp.agg_call.child
        assert isinstance(child, Filter)
        base = dict(DEFAULT_PARAMS[q])
        env = build_env(prog, catalog, {**base, corr: 0})
        # host scalars in the dtypes JAX gives them (f32, i32)
        params = {k: np.asarray(jnp.asarray(v)) for k, v in base.items()}
        params.update({f: np.asarray(jnp.asarray(env[f]))
                       for f in rp.agg_call.aggregate.fields if f in env})
        call = AggCall(child.child, rp.agg_call.aggregate,
                       rp.agg_call.param_binding, rp.agg_call.ordered,
                       rp.agg_call.sort_keys, rp.agg_call.sort_desc,
                       group_keys=(gk,),
                       max_groups=catalog[DOMAIN[q]].capacity)
        out[q] = (call, prog, params)
    return out


def _route(plan, catalog, params, srv=None):
    """The route the engine takes for a grouped ``plan``
    (``engine.grouped_route`` over the abstract input table), served by
    ``srv`` — with the bound it infers and the slot table it provides,
    when it does — or run directly."""
    import dataclasses
    import jax
    from repro.relational import execute
    from repro.relational.engine import grouped_route
    t = jax.eval_shape(lambda c: execute(plan.child, c, params), catalog)
    cached = False
    if srv is not None:
        d = srv.describe(plan)
        plan = dataclasses.replace(plan, max_groups=d["max_groups"])
        cached = d["slot_scan"] is not None
    return grouped_route(plan, t, params, cached=cached)


def _print_route(name, route, t_setup, t_serve):
    print(f"{name}: route={route.name} kernel={route.kernel} "
          f"launched_grid_steps={route.grid_steps} "
          f"setup_compile_s={t_setup:.2f} serve_s={t_serve:.3f}",
          flush=True)


def _timed(fn):
    import jax
    t = time.perf_counter()
    out = fn()
    jax.block_until_ready(jax.tree_util.tree_leaves(
        out.columns if hasattr(out, "columns") else out))
    return out, time.perf_counter() - t


def _kernel_text(check, srv, plan, params, name, kernel, on_tpu):
    if not (kernel and on_tpu):
        return
    check("tpu_custom_call" in srv.compiled_text(plan, params),
          f"{name}: compiled executable holds the kernel (tpu_custom_call)")


def _guard_clean(check, srv, name):
    g = srv.guard_stats
    check(g.backend_failures == g.degraded_launches == g.breaker_trips == 0,
          f"{name}: backend_failures={g.backend_failures} "
          f"degraded_launches={g.degraded_launches} "
          f"breaker_trips={g.breaker_trips}")


def dashboard_table(scale: float, seed: int):
    """Dashboard fact table: 500 tile keys, spare capacity for ingest."""
    from repro.relational import Table
    import jax.numpy as jnp
    rng = np.random.default_rng(seed + 1)
    n = max(1024, int(400_000 * scale))
    spare = INGEST_BATCHES * INGEST_ROWS
    cols = {"k": rng.integers(0, 500, n + spare).astype(np.int32),
            "v": rng.integers(-4, 5, n + spare).astype(np.float32),
            "w": rng.integers(0, 100, n + spare).astype(np.float32)}
    valid = np.arange(n + spare) < n
    t = Table({c: jnp.asarray(a) for c, a in cols.items()}, jnp.asarray(valid))
    return t, rng


def tiles():
    from repro.relational.plan import GroupAgg, Scan
    from repro.relational.tpch import SCHEMAS
    t_cols = ("k", "v", "w")
    return {
        "tile_rev": GroupAgg(Scan("T", t_cols), ("k",),
                             (("rev", "sum", "v"), ("n", "count", None),
                              ("hi", "max", "v"))),
        "tile_avg": GroupAgg(Scan("T", t_cols), ("k",),
                             (("avg_w", "mean", "w"), ("lo", "min", "v"))),
        # the sort-free route at scale: 60M line items over the 2556-day
        # ship-date domain, declared — without a bound the direct executor
        # sizes the moment tensor by the 60M-row capacity (out of memory)
        "tile_ship": GroupAgg(Scan("LINEITEM", SCHEMAS["LINEITEM"]),
                              ("l_shipdate",),
                              (("qty", "sum", "l_quantity"),
                               ("hi", "max", "l_extendedprice"),
                               ("lo", "min", "l_discount")),
                              max_groups=2556),
    }


def check_tile(check, name, out, h, table, key, aggs):
    present, ref = numpy_tile(h, table, key, aggs)
    got = out.to_numpy()
    for agg_name, op, _col in aggs:
        _check_groups(check, f"{name}.{agg_name}", np.asarray(got[key]),
                      np.asarray(got[agg_name], np.float64), present,
                      np.asarray(ref[agg_name], np.float64),
                      exact=op != "mean")


def cursor_reference(check, q, prog, catalog, h, served, rng):
    """``run_cursor`` over the rows the correlated cursor query selects
    for a few sampled keys, against the served grouped result."""
    import jax
    import jax.numpy as jnp
    from benchmarks.queries import DEFAULT_PARAMS, QUERIES
    from repro.core import run_cursor
    from repro.relational import Table

    _f, corr, gk = QUERIES[q]
    src = {"Q2": "PARTSUPP", "Q13": "ORDERS"}.get(q, "LINEITEM")
    keys, vals = served
    ret = prog.returns[0]
    cap = CURSOR_ROWS[q]
    cursor = jax.jit(lambda cat, p: run_cursor(prog, cat, p))
    for k in rng.choice(keys, size=min(3, len(keys)), replace=False):
        idx = np.flatnonzero(h[src][gk] == k)
        if not check(len(idx) <= cap, f"{q}: key {k} has {len(idx)} rows"):
            continue
        cols = {}
        for c, a in h[src].items():
            pad = np.zeros(cap, a.dtype)
            pad[:len(idx)] = a[idx]
            cols[c] = jnp.asarray(pad)
        sub = {"SUPPLIER": catalog["SUPPLIER"]}
        sub[src] = Table(cols, jnp.asarray(np.arange(cap) < len(idx)))
        ref = cursor(sub, {**DEFAULT_PARAMS[q], corr: np.int32(k)})
        want = np.asarray(ref[ret])
        got = vals[np.flatnonzero(keys == k)[0]]
        check(np.array_equal(np.asarray(got, want.dtype), want),
              f"{q}: run_cursor({corr}={k}) = {want} matches served {got}")


def run_one_chip(args, check, device) -> None:
    import jax
    from repro.core import aggify, run_rewritten
    from repro.relational.tpch import gen_tpch
    from repro.serve import AggServer, ServeRequest
    from benchmarks.queries import DEFAULT_PARAMS, QUERIES

    on_tpu = device.platform == "tpu"
    t0 = time.perf_counter()
    catalog = gen_tpch(scale=args.scale, seed=args.seed)
    T, trng = dashboard_table(args.scale, args.seed)
    catalog["T"] = T
    jax.block_until_ready([t.columns for t in catalog.values()])
    h = _host(catalog)
    h["T"] = {c: np.array(a) for c, a in T.columns.items()}   # writable
    h["T"]["__valid__"] = np.array(T.mask())
    stats = device.memory_stats() or {}
    print(f"setup: catalog at scale {args.scale} seed {args.seed}: "
          + ", ".join(f"{n}={t.capacity}" for n, t in catalog.items())
          + f"; bytes_in_use={stats.get('bytes_in_use', 'n/a')} "
          f"({time.perf_counter() - t0:.1f}s)", flush=True)

    srv = AggServer(catalog, max_batch=8)
    rng = np.random.default_rng(args.seed + 2)
    calls = grouped_calls(catalog)

    # grouped Aggify+ loops through the server
    for q, (call, prog, params) in calls.items():
        out, t_setup = _timed(lambda: srv.execute(call, params))
        again, t_serve = _timed(lambda: srv.execute(call, params))
        route = _route(call, catalog, params, srv)
        _print_route(q, route, t_setup, t_serve)
        gk = call.group_keys[0]
        ret = prog.returns[0]
        keys, vals = _result_map(out, gk, ret)
        present, ref = numpy_grouped(q, h, {k: float(v) if v.ndim == 0
                                            else v for k, v in params.items()})
        _check_groups(check, q, keys, vals, present, ref)
        k2, v2 = _result_map(again, gk, ret)
        check(np.array_equal(k2, keys) and np.array_equal(v2, vals),
              f"{q}: cached re-serve is identical")
        _kernel_text(check, srv, call, params, q, route.kernel, on_tpu)
        cursor_reference(check, q, prog, catalog, h, (keys, vals), rng)

    # concurrent submits: Q2 at two lower bounds coalesces into one
    # vmapped launch; the parameterless-shape loops reuse their executable
    call2, _p2, params2 = calls["Q2"]
    lbs = (4.0, 10.0)
    t = time.perf_counter()
    futs = [srv.submit(call2, {**params2, "lb": np.float32(lb)})
            for lb in lbs]
    futs += [srv.submit(calls[q][0], calls[q][2]) for q in ("Q13", "Q21")]
    outs = [f.result(timeout=900) for f in futs]
    print(f"submit: {len(futs)} requests in "
          f"{time.perf_counter() - t:.2f}s (batches={srv.stats.batches})",
          flush=True)
    for lb, out in zip(lbs, outs):
        present, ref = numpy_grouped(
            "Q2", h, {"lb": lb, "minCost": float(params2["minCost"])})
        keys, vals = _result_map(out, "ps_partkey", "suppName")
        _check_groups(check, f"Q2 submit lb={lb}", keys, vals, present, ref)
    for q, out in zip(("Q13", "Q21"), outs[2:]):
        call, prog, params = calls[q]
        present, ref = numpy_grouped(q, h, {})
        keys, vals = _result_map(out, call.group_keys[0], prog.returns[0])
        _check_groups(check, f"{q} submit", keys, vals, present, ref)

    # whole-table loops through run_rewritten
    for q in ("Q14", "Q19"):
        rp = aggify(QUERIES[q][0]())
        prm = DEFAULT_PARAMS[q]
        got, dt = _timed(lambda: jax.jit(
            lambda cat: run_rewritten(rp, cat, prm))(catalog))
        ret = rp.returns[0]
        want = numpy_whole(q, h, prm)
        check(np.allclose(float(got[ret]), want, rtol=1e-4),
              f"{q}: run_rewritten {ret}={float(got[ret]):.6g} vs numpy "
              f"{want:.6g} ({dt:.2f}s incl. compile)")

    # dashboard tiles, then ingest folded into a resident tile
    for name, tile in tiles().items():
        table = tile.child.table
        out, t_setup = _timed(lambda: srv.execute(tile, {}))
        f = srv.submit(tile, {})
        _o, t_serve = _timed(lambda: f.result(timeout=900))
        route = _route(tile, catalog, {}, srv)
        _print_route(name, route, t_setup, t_serve)
        check_tile(check, name, out, h, table, tile.keys[0], tile.aggs)
        _kernel_text(check, srv, tile, {}, name, route.kernel, on_tpu)

    tile = tiles()["tile_rev"]
    srv.serve(ServeRequest(tile, consistency="epoch"))     # seeds residency
    hT = h["T"]
    for b in range(INGEST_BATCHES):
        batch = {"k": trng.integers(0, 600, INGEST_ROWS).astype(np.int32),
                 "v": trng.integers(-4, 5, INGEST_ROWS).astype(np.float32),
                 "w": trng.integers(0, 100, INGEST_ROWS).astype(np.float32)}
        version = srv.ingest("T", batch)
        holes = np.flatnonzero(~hT["__valid__"])[:INGEST_ROWS]
        for c, a in batch.items():
            hT[c][holes] = a
        hT["__valid__"][holes] = True
    res = srv.serve(ServeRequest(tile, consistency="epoch"))
    print(f"ingest: {INGEST_BATCHES}x{INGEST_ROWS} rows, folds="
          f"{srv.stats.folds}, epoch version={res.version} "
          f"(acknowledged {version})", flush=True)
    check(res.version == version and srv.stats.folds == INGEST_BATCHES,
          "ingest: epoch read is at the last acknowledged version")
    check_tile(check, "ingest", res.table, h, "T", "k", tile.aggs)
    _guard_clean(check, srv, "serving guard")
    srv.close()


def numpy_whole(q: str, h: dict, prm: dict) -> float:
    li, part = h["LINEITEM"], h["PART"]
    promo = part["p_type_promo"][li["l_partkey"]]
    rev = (li["l_extendedprice"].astype(np.float64)
           * (1.0 - li["l_discount"].astype(np.float64)))
    if q == "Q14":
        w = (li["l_shipdate"] >= prm["d0"]) & (li["l_shipdate"] < prm["d1"])
        return 100.0 * rev[w & promo].sum() / (1e-9 + rev[w].sum())
    qty = li["l_quantity"]
    return rev[(qty >= prm["qlo"]) & (qty <= prm["qhi"]) & promo].sum()


def run_four_chips(args, check, devices) -> None:
    """LINEITEM row-sharded over a 4-device mesh: a grouped loop and a
    tile on it through the direct executors (one jitted ``execute``,
    which launches the kernel per shard under ``shard_map``) and through
    ``AggServer``, each equal to the same call on one device and to
    numpy."""
    import jax
    from jax.sharding import Mesh
    from repro.relational import execute
    from repro.relational.tpch import gen_tpch
    from repro.serve import AggServer

    on_tpu = devices[0].platform == "tpu"
    t0 = time.perf_counter()
    catalog = gen_tpch(scale=args.scale, seed=args.seed)
    jax.block_until_ready([t.columns for t in catalog.values()])
    h = _host({n: catalog[n] for n in ("LINEITEM", "ORDERS")})
    mesh = Mesh(np.asarray(devices[:4]), ("data",))
    sharded = dict(catalog)
    sharded["LINEITEM"] = catalog["LINEITEM"].shard_rows(mesh, "data")
    jax.block_until_ready(sharded["LINEITEM"].columns)
    print(f"setup: LINEITEM={catalog['LINEITEM'].capacity} rows over "
          f"{mesh.shape['data']} devices "
          f"({time.perf_counter() - t0:.1f}s)", flush=True)

    one = AggServer(catalog)
    srv = AggServer(sharded)
    calls = grouped_calls(catalog)
    # Q18: the sorted route, pruned launch per shard; tile_ship: the
    # sort-free route — per-shard slotting in the direct executor, the
    # cached global slots in the server (Q21 shares Q18's path)
    call, prog, params = calls["Q18"]
    plans = [("Q18", call, params, prog.returns[0]),
             ("tile_ship", tiles()["tile_ship"], {}, "qty")]
    for name, plan, params, col in plans:
        key = plan.group_keys[0] if hasattr(plan, "group_keys") \
            else plan.keys[0]
        base, t1 = _timed(lambda: one.execute(plan, params))

        t = time.perf_counter()
        compiled = jax.jit(lambda c, p, plan=plan: execute(plan, c, p)
                           ).lower(sharded, params).compile()
        direct, _ = _timed(lambda: compiled(sharded, params))
        t2 = time.perf_counter() - t
        served, t3 = _timed(lambda: srv.execute(plan, params))
        print(f"{name}: one-device {t1:.2f}s, sharded executor {t2:.2f}s, "
              f"sharded server {t3:.2f}s (each incl. compile)", flush=True)
        for who, via in (("executor", None), ("server", srv)):
            r = _route(plan, sharded, params, via)
            print(f"{name} sharded {who}: route={r.name} kernel={r.kernel} "
                  f"rows_per_shard={r.rows} "
                  f"launched_grid_steps={r.grid_steps}", flush=True)
        bk, bv = _result_map(base, key, col)
        order = np.argsort(bk)
        for route, out in (("executor", direct), ("server", served)):
            k, v = _result_map(out, key, col)
            o = np.argsort(k)
            check(np.array_equal(k[o], bk[order])
                  and np.array_equal(v[o], bv[order]),
                  f"{name}: sharded {route} equals the one-device result")
        if name == "tile_ship":
            check_tile(check, name, served, h, "LINEITEM", key, plan.aggs)
        else:
            present, ref = numpy_grouped(name, h, {})
            _check_groups(check, name, bk, bv, present, ref)
        if on_tpu:
            check("tpu_custom_call" in compiled.as_text(),
                  f"{name} sharded executor: compiled program holds the "
                  f"kernel (tpu_custom_call)")
        _kernel_text(check, srv, plan, params, f"{name} sharded server",
                     True, on_tpu)
    _guard_clean(check, srv, "sharded serving guard")
    _guard_clean(check, one, "one-device serving guard")
    srv.close()
    one.close()


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--chips", type=int, choices=(1, 4), default=1)
    ap.add_argument("--seed", type=int, default=0)
    args = ap.parse_args()
    args.scale = 10.0
    _import_repo()

    import jax
    devices = jax.devices()
    dev = devices[0]
    if dev.platform != "tpu":
        print(f"chip_smoke: needs a TPU; JAX found {dev.platform}",
              file=sys.stderr)
        return 1
    if len(devices) < args.chips:
        print(f"chip_smoke: --chips {args.chips} but JAX found "
              f"{len(devices)} devices", file=sys.stderr)
        return 1
    from repro.launch.compile_cache import configure_compile_cache
    print(f"device: {dev.device_kind} x{len(devices)}; compile cache "
          f"{configure_compile_cache()}", flush=True)

    check = Checks()
    t0 = time.perf_counter()
    if args.chips == 4:
        run_four_chips(args, check, devices)
    else:
        run_one_chip(args, check, dev)
    print(f"total {time.perf_counter() - t0:.1f}s; "
          f"{len(check.failed)} failed checks", flush=True)
    if check.failed:
        for f in check.failed:
            print(f"chip_smoke: FAIL: {f}", file=sys.stderr)
        return 1
    print(json.dumps({"ok": True, "device": {
        "platform": dev.platform, "kind": dev.device_kind,
        "count": len(devices)}}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
