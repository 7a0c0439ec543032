"""Cases of the shard-local sorted route, run by
``tests/test_shard_local_group_sort.py`` in a process with four host
devices (``XLA_FLAGS=--xla_force_host_platform_device_count=4``).

Each case is a table of 2,048 rows split over the four devices.  A cursor
loop (a guarded sum and a count per key, Aggify's grouped form) and a
``GroupAgg`` run over it on one device and row-split, under each merge
the case admits: ``'ranges'`` where the rows are in key order and the
split declares it, ``'keys'`` where a dense bound is declared.  The
result is printed as one JSON line: per case, the merges taken and
every mismatch against the one-device run and the numpy reference (sums
of integer-valued floats, so every order of addition is exact).  The
first case also breaks a declared key range, which must raise or poison.
"""
import json
import sys

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import Mesh

from repro.core import Assign, Const, CursorLoop, If, Program, Var, aggify, let
from repro.relational import Scan, Table, execute
from repro.relational.engine import grouped_route
from repro.relational.plan import AggCall, GroupAgg

N = 2048
SHARD = N // 4


def loop_call(max_groups):
    prog = Program(
        "sumCount", params=(),
        pre=[let("tot", Const(0.0)), let("cnt", Const(0.0))],
        loop=CursorLoop(
            Scan("T", ("k", "v")), fetch=[("c", "v")],
            body=[If(Var("c") > Const(20.0),
                     [Assign("tot", Var("tot") + Var("c"))]),
                  Assign("cnt", Var("cnt") + Const(1.0))]),
        post=[], returns=("tot", "cnt"))
    c = aggify(prog).agg_call
    return AggCall(c.child, c.aggregate, c.param_binding, c.ordered,
                   c.sort_keys, c.sort_desc, group_keys=("k",),
                   max_groups=max_groups, mode="fused")


def group_agg(max_groups):
    """Sums and counts, and the row of each group's first least value:
    the last needs the rows of a group in table order."""
    return GroupAgg(Scan("T", ("k", "v", "i")), ("k",),
                    (("s", "sum", "v"), ("n", "count", None),
                     ("mx", "max", "v"), ("first", "argmin", ("v", "i"))),
                    max_groups=max_groups)


def reference(k, v, valid):
    """Per key, in key order: the loop's guarded sum and count, and the
    GroupAgg's sum, count, max and first row of the least value."""
    i = np.flatnonzero(valid)
    k, v = k[valid], v[valid]
    uk, inv = np.unique(k, return_inverse=True, equal_nan=True)
    inv = inv.reshape(-1)
    tot = np.bincount(inv, np.where(v > 20, v, 0), len(uk))
    cnt = np.bincount(inv, minlength=len(uk)).astype(np.float64)
    s = np.bincount(inv, v, len(uk))
    mx = np.full(len(uk), -np.inf)
    np.maximum.at(mx, inv, v)
    mn = np.full(len(uk), np.inf)
    np.minimum.at(mn, inv, v)
    first = np.full(len(uk), np.inf)
    np.minimum.at(first, inv, np.where(v == mn[inv], i, np.inf))
    return uk, {"tot": tot, "cnt": cnt, "s": s, "n": cnt, "mx": mx,
                "first": first}


def by_key(res, cols):
    """Valid result rows in key order (NaN last)."""
    o = np.argsort(res["k"], kind="stable")
    return res["k"][o], {c: np.asarray(res[c], np.float64)[o] for c in cols}


def cases(rng):
    """name → [(keys, values, valid, merges)]: ``merges`` maps each merge
    a table runs under to (ordered_by, max_groups)."""
    v = rng.integers(0, 50, N).astype(np.float32)
    every = np.ones(N, bool)
    out = {}
    # Q18-like: sparse sorted keys, 1..7 rows each, almost every group on
    # one shard; a bound above a shard's rows leaves only the ranges
    lines = np.repeat(np.arange(N), rng.integers(1, 8, N))[:N]
    k = (((lines >> 3) << 5) | (lines & 7)).astype(np.int32)
    out["one_shard"] = [(k, v, every, {"ranges": (("k",), 700)})]
    # Q21-like: a hundred keys, every one on every shard
    k = rng.integers(1, 101, N).astype(np.int32)
    out["every_shard"] = [(k, v, every, {"keys": ((), 100)})]
    # one group covers the end of shard 0, all of shard 1 and the start
    # of shard 2; its neighbours end and start on a boundary
    k = rng.integers(0, 60, N).astype(np.int32)
    k[SHARD - 100:2 * SHARD + 100] = 30
    k = np.sort(k)
    out["straddles"] = [(k, v, every, {"ranges": (("k",), 60),
                                       "keys": ((), 60)})]
    # shard 2 holds half the keys and shard 3 no valid row
    k = rng.integers(0, 100, N).astype(np.int32)
    k[2 * SHARD:3 * SHARD] %= 50
    valid = np.arange(N) < 3 * SHARD
    ks = np.sort(k[:3 * SHARD])
    k_sorted = np.concatenate([ks, np.full(SHARD, ks[-1])]).astype(np.int32)
    out["absent"] = [(k, v, valid, {"keys": ((), 100)}),
                     (k_sorted, v, valid, {"ranges": (("k",), 100)})]
    # shard 1 holds no valid row, and one group runs from the end of
    # shard 0 across it into the start of shard 2
    k = rng.integers(0, 80, N).astype(np.int32)
    k[SHARD - 50:2 * SHARD + 50] = 40
    k = np.sort(k)
    valid = (np.arange(N) < SHARD) | (np.arange(N) >= 2 * SHARD)
    out["empty_shard"] = [(k, v, valid, {"ranges": (("k",), 80),
                                         "keys": ((), 80)})]
    # a third of the rows invalid, the first and last rows among them
    k = np.sort(rng.integers(0, 90, N)).astype(np.int32)
    valid = rng.random(N) > 0.33
    valid[[0, SHARD - 1, SHARD, N - 1]] = False
    out["invalid"] = [(k, v, valid, {"ranges": (("k",), 90),
                                     "keys": ((), 90)})]
    # float keys with NaN (one bit pattern) and both zeros
    pool = np.array([-1.5, 0.0, -0.0, 2.5, np.nan, 7.0], np.float32)
    k = pool[rng.integers(0, len(pool), N)]
    out["nan_key"] = [(k, v, every, {"keys": ((), 8)}),
                      (np.sort(k), v, every, {"ranges": (("k",), 8)})]
    return out


def run(name, k, v, valid, merges, mesh):
    uk, ref = reference(k, v, valid)
    t = Table({"k": jnp.asarray(k), "v": jnp.asarray(v),
               "i": jnp.arange(N, dtype=jnp.int32)}, jnp.asarray(valid))
    env = {"tot": jnp.float32(0.0), "cnt": jnp.float32(0.0)}
    errors, taken = [], []
    for merge, (order, max_groups) in merges.items():
        sh = t.shard_rows(mesh, "data", ordered_by=order)
        for plan, e, cols in ((loop_call(max_groups), env, ("tot", "cnt")),
                              (group_agg(max_groups), None,
                               ("s", "n", "mx", "first"))):
            route = grouped_route(plan, sh, e)
            taken.append(route.merge)
            one = execute(plan, {"T": t}, e).to_numpy()
            for how, got in (
                    ("eager", execute(plan, {"T": sh}, e).to_numpy()),
                    ("jit", jax.jit(lambda c, e: execute(plan, c, e))(
                        {"T": sh}, e).to_numpy())):
                gk, gv = by_key(got, cols)
                ok, ov = by_key(one, cols)
                where = f"{name}/{merge}/{type(plan).__name__}/{how}"
                if not (np.array_equal(gk, uk, equal_nan=True)
                        and np.array_equal(ok, uk, equal_nan=True)):
                    errors.append(f"{where}: keys")
                    continue
                for c in cols:
                    if not np.array_equal(gv[c], ref[c]):
                        errors.append(f"{where}: {c} against the reference")
                    if not np.array_equal(gv[c], ov[c]):
                        errors.append(f"{where}: {c} against one device")
    return {"merges": taken, "errors": errors}


def broken_ranges(mesh):
    """A split that declares key ranges its rows do not keep: an eager
    run raises, a traced one comes back poisoned."""
    k = np.random.default_rng(5).integers(0, 50, N).astype(np.int32)
    t = Table.from_columns(k=k, v=np.ones(N, np.float32),
                           i=np.arange(N, dtype=np.int32)).shard_rows(
        mesh, "data", ordered_by=("k",))
    plan = group_agg(50)
    errors = []
    try:
        execute(plan, {"T": t})
        errors.append("broken ranges: the eager run did not raise")
    except ValueError:
        pass
    got = jax.jit(lambda c: execute(plan, c))({"T": t}).to_numpy()
    if not np.all(np.isnan(got["s"])):
        errors.append("broken ranges: the traced result is not poisoned")
    return errors


def overflow(mesh):
    """More groups than the declared bound, most of them on shard 2
    alone: under either merge an eager run raises and a traced result
    comes back poisoned whole, every entry of every column its
    sentinel."""
    from repro.relational.group_bound import (GroupBoundOverflow,
                                              poison_sentinel)
    per_shard = [np.repeat(np.arange(lo, lo + m), -(-SHARD // m))[:SHARD]
                 for lo, m in ((0, 10), (10, 10), (20, 180), (200, 10))]
    k = np.concatenate(per_shard).astype(np.int32)
    t = Table.from_columns(k=k, v=np.ones(N, np.float32),
                           i=np.arange(N, dtype=np.int32))
    env = {"tot": jnp.float32(0.0), "cnt": jnp.float32(0.0)}
    errors, taken = [], []
    for merge, order in (("ranges", ("k",)), ("keys", ())):
        sh = t.shard_rows(mesh, "data", ordered_by=order)
        for plan, e in ((loop_call(100), env), (group_agg(100), None)):
            where = f"overflow/{merge}/{type(plan).__name__}"
            taken.append(grouped_route(plan, sh, e).merge)
            try:
                execute(plan, {"T": sh}, e)
                errors.append(f"{where}: the eager run did not raise")
            except GroupBoundOverflow:
                pass
            got = jax.jit(lambda c, e: execute(plan, c, e))({"T": sh}, e)
            for c, col in got.columns.items():
                bad = np.asarray(poison_sentinel(col.dtype))
                if not np.array_equal(np.asarray(col),
                                      np.broadcast_to(bad, col.shape),
                                      equal_nan=col.dtype.kind == "f"):
                    errors.append(f"{where}: {c} is not poisoned whole")
    return {"merges": taken, "errors": errors}


def main():
    assert jax.device_count() == 4, jax.device_count()
    mesh = Mesh(np.asarray(jax.devices()), ("data",))
    rng = np.random.default_rng(11)
    out = {}
    for name, tables in cases(rng).items():
        got = [run(name, *tab, mesh) for tab in tables]
        out[name] = {"merges": [m for g in got for m in g["merges"]],
                     "errors": [e for g in got for e in g["errors"]]}
    out["one_shard"]["errors"] += broken_ranges(mesh)
    out["overflow"] = overflow(mesh)
    print(json.dumps(out))


if __name__ == "__main__":
    sys.exit(main())
