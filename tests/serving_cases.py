"""Shared case runner for the serving differential fuzzer.

A *case* is a plain dict of ints/strings/floats — deterministically
expanded into (table, plan, parameter stream) by ``build_case`` — so the
hypothesis fuzzer (tests/test_serving_differential.py) and the checked-in
seed corpus (tests/test_serving_corpus.py) replay the exact same code
path; a fuzzer failure minimizes to a dict that goes straight into
``CORPUS`` and reproduces without hypothesis installed.

``run_case`` asserts bit-for-bit parity across every route that applies:

* sort-free vs the numpy oracle (grouping by canonical key words — the
  bitwise semantics keyslot.py documents: ±0 collapse, NaNs group per
  bit pattern);
* sorted vs sort-free and sorted vs oracle — skipped when the case
  carries NaN keys, where the routes *diverge by design* (the sorted
  route's value-equality adjacency splinters NaNs into one group per
  row; the bitwise route groups them);
* server-cached (compiled-plan + slot-table caches) vs fresh, twice, so
  the second call exercises a warm cache;
* batched (concurrent ``submit`` coalesced into one vmapped launch) vs
  sequential.

Aggregate inputs are integer-valued and small (|v| ≤ 2, |w| ≤ 8) so
every float32 summation order is exact and "parity" can mean *equality*,
not tolerance."""
from __future__ import annotations

import os
from contextlib import contextmanager

import jax.numpy as jnp
import numpy as np

from repro.core.loop_ir import Col, Var
from repro.relational import Table, execute
from repro.relational import keyslot
from repro.relational.plan import Filter, GroupAgg, Scan
from repro.serve import AggServer

#: GroupAgg ops the fuzzer draws from; arg-extremum ops aggregate the
#: ("v", "w") pair (payload w of the first row attaining v's extremum)
OPS = ("sum", "count", "min", "max", "mean", "prod", "argmin", "argmax")

#: key-column generators by drawn dtype name.  64-bit inputs
#: intentionally pass through jnp's default-config canonicalization
#: (int64→int32, float64→float32 when x64 is off) — the parity contract
#: is over the table as stored, whatever the config stores.
KEY_DTYPES = ("int32", "int16", "int64", "float32", "float64", "bool")

#: float key value pool: exercises ±0 collapse; NaN appended per-case
_FLOAT_KEYS = (0.0, -0.0, 1.5, -2.25, 3.5, -0.5)


@contextmanager
def _env(name: str, value):
    old = os.environ.get(name)
    try:
        if value is None:
            os.environ.pop(name, None)
        else:
            os.environ[name] = value
        yield
    finally:
        if old is None:
            os.environ.pop(name, None)
        else:
            os.environ[name] = old


def build_case(case: dict):
    """Expand a case dict into (table, plan, param-env stream)."""
    rng = np.random.default_rng(case["seed"])
    n = case["n"]
    card = case.get("card", 5)
    nan_keys = case.get("nan_keys", False)
    cols = {}
    keys = []
    for i, dt in enumerate(case["key_dtypes"]):
        name = f"k{i}"
        keys.append(name)
        if dt == "bool":
            cols[name] = rng.integers(0, 2, n).astype(bool)
        elif dt.startswith("int"):
            cols[name] = rng.integers(0, card, n).astype(dt)
        else:
            pool = list(_FLOAT_KEYS[:max(2, card)])
            if nan_keys:
                pool[0] = np.nan
            cols[name] = np.asarray(pool, dt)[rng.integers(0, len(pool), n)]
    cols["v"] = rng.integers(-2, 3, n).astype(np.float32)
    cols["w"] = rng.integers(-8, 9, n).astype(np.float32)
    valid = rng.random(n) >= case.get("invalid_frac", 0.0)
    if not valid.any():
        valid[0] = True
    t = Table({k: jnp.asarray(v) for k, v in cols.items()},
              jnp.asarray(valid))

    schema = tuple(keys) + ("v", "w")
    child = Scan("T", schema)
    if case.get("filtered", False):
        child = Filter(child, Col("v") >= Var("lo"))
    aggs = []
    for i, op in enumerate(case["aggs"]):
        col = None if op == "count" else \
            ("v", "w") if op in ("argmin", "argmax") else "v"
        aggs.append((f"a{i}", op, col))
    plan = _intern(GroupAgg(child, tuple(keys), tuple(aggs),
                            max_groups=case.get("max_groups")))
    envs = [{"lo": float(p)} for p in case.get("params", ())] \
        if case.get("filtered", False) else [{}]
    return t, plan, tuple(keys), tuple(aggs), envs


# one plan object per structure: the server caches per plan identity, so
# interning lets 200 fuzz examples share executables instead of each
# example retracing its structurally-identical plan
_PLANS: dict = {}


def _intern(plan):
    return _PLANS.setdefault(plan, plan)


# one server across all cases — exactly how production reuses caches;
# update_table per case exercises the invalidation path constantly
_SERVER = None


def server() -> AggServer:
    global _SERVER
    if _SERVER is None:
        _SERVER = AggServer({"T": Table.from_columns(z=np.zeros(1))},
                            max_batch=8, batch_window_s=0.0)
    return _SERVER


# -- oracle -----------------------------------------------------------------


def _group_rows(t: Table, keys, env):
    """Row-index lists per group, keyed by canonical-word byte tuples, in
    first-appearance order — the bitwise grouping semantics."""
    words = np.asarray(keyslot.key_words_for(t.columns[k] for k in keys))
    mask = np.asarray(t.mask())
    if env:   # parameterized filter semantics of the fuzz plan
        mask = mask & (np.asarray(t.columns["v"]) >= np.float32(env["lo"]))
    groups: dict = {}
    for i in np.nonzero(mask)[0]:
        groups.setdefault(words[i].tobytes(), []).append(int(i))
    return groups


def oracle(t: Table, keys, aggs, env) -> dict:
    """numpy reference: canonical-word grouping + float32 aggregation in
    the same formulas the engine uses (exact on integer-valued data)."""
    v = np.asarray(t.columns["v"])
    w = np.asarray(t.columns["w"])
    out = {}
    for wkey, rows in _group_rows(t, keys, env).items():
        gv = v[rows].astype(np.float32)
        vals = {}
        for name, op, _col in aggs:
            if op == "sum":
                vals[name] = np.float32(gv.sum())
            elif op == "count":
                vals[name] = np.int32(len(rows))
            elif op == "min":
                vals[name] = np.float32(gv.min())
            elif op == "max":
                vals[name] = np.float32(gv.max())
            elif op == "mean":
                vals[name] = np.float32(gv.sum()) / np.float32(len(rows))
            elif op == "prod":
                vals[name] = np.float32(np.prod(gv))
            elif op in ("argmin", "argmax"):
                best = gv.min() if op == "argmin" else gv.max()
                first = rows[int(np.nonzero(gv == best)[0][0])]
                vals[name] = np.float32(w[first])
            else:
                raise ValueError(op)
        out[wkey] = vals
    return out


def result_groups(table: Table, keys, aggs) -> dict:
    """A result Table's valid rows as {canonical-word bytes: {agg: value}}
    — the order-insensitive form every route comparison uses."""
    words = np.asarray(keyslot.key_words_for(table.columns[k] for k in keys))
    mask = np.asarray(table.mask())
    out = {}
    for i in np.nonzero(mask)[0]:
        wkey = words[i].tobytes()
        assert wkey not in out, "duplicate group row in result"
        out[wkey] = {name: np.asarray(table.columns[name])[i]
                     for name, _op, _col in aggs}
    return out


def assert_same_groups(got: dict, want: dict, label: str):
    assert set(got) == set(want), \
        f"{label}: group sets differ ({len(got)} vs {len(want)})"
    for wkey, vals in want.items():
        for name, ref in vals.items():
            g = got[wkey][name]
            assert np.array_equal(np.asarray(g), np.asarray(ref),
                                  equal_nan=True), \
                f"{label}: {name} differs: {g!r} != {ref!r}"


# -- the differential runner ------------------------------------------------


def run_case(case: dict) -> None:
    t, plan, keys, aggs, envs = build_case(case)
    cat = {"T": t}
    srv = server()
    srv.update_table("T", t)

    for env in envs:
        ref = oracle(t, keys, aggs, env)
        # fresh sort-free (the default route when a bound is declared)
        r_sf = execute(plan, cat, env)
        assert_same_groups(result_groups(r_sf, keys, aggs), ref,
                           "sortfree vs oracle")
        # fresh sorted route
        with _env("REPRO_GROUPAGG_SORTFREE", "off"):
            r_sorted = execute(plan, cat, env)
        if not case.get("nan_keys", False):
            assert_same_groups(result_groups(r_sorted, keys, aggs), ref,
                               "sorted vs oracle")
        # server-cached vs fresh — twice, so the second run is warm
        for _ in range(2):
            r_cached = srv.execute(plan, env)
            assert_same_groups(result_groups(r_cached, keys, aggs), ref,
                               "cached vs fresh")

    # batched vs sequential: the whole parameter stream concurrently
    if len(envs) > 1:
        futs = [srv.submit(plan, env) for env in envs]
        for fut, env in zip(futs, envs):
            got = result_groups(fut.result(timeout=120), keys, aggs)
            want = result_groups(srv.execute(plan, env), keys, aggs)
            assert_same_groups(got, want, "batched vs sequential")


# -- seed corpus ------------------------------------------------------------
# Regressions replay without hypothesis: every past fuzzer failure (and a
# hand-picked spread of the generator's corners) lives here as data.

CORPUS = [
    # single int key, the moment family, declared bound
    {"seed": 1, "n": 160, "key_dtypes": ("int32",), "card": 6,
     "aggs": ("sum", "count", "min", "max"), "max_groups": 24},
    # inferred bound (max_groups absent): server sketches + validates
    {"seed": 2, "n": 192, "key_dtypes": ("int32",), "card": 5,
     "aggs": ("sum", "mean")},
    # float key incl. ±0 collapse
    {"seed": 3, "n": 150, "key_dtypes": ("float32",), "card": 6,
     "aggs": ("sum", "prod"), "max_groups": 16},
    # NaN keys: bitwise grouping (sorted-route comparison skipped)
    {"seed": 4, "n": 144, "key_dtypes": ("float32",), "card": 4,
     "nan_keys": True, "aggs": ("sum", "count"), "max_groups": 16},
    # 64-bit key dtypes through default-config canonicalization
    {"seed": 5, "n": 176, "key_dtypes": ("int64", "float64"), "card": 3,
     "aggs": ("max", "argmin"), "max_groups": 32},
    # composite key with bool, arg-extrema, invalid rows
    {"seed": 6, "n": 208, "key_dtypes": ("bool", "int16"), "card": 4,
     "invalid_frac": 0.3, "aggs": ("argmax", "argmin", "sum"),
     "max_groups": 16},
    # parameterized filter child: executable cache + batching, slots
    # derived inside the trace (child is not a Scan)
    {"seed": 7, "n": 168, "key_dtypes": ("int32",), "card": 5,
     "filtered": True, "params": (-1.0, 0.0, 1.0, 2.0),
     "aggs": ("sum", "count", "max"), "max_groups": 16},
    # repeated parameters: same-shape requests coalesce
    {"seed": 8, "n": 160, "key_dtypes": ("int32", "float32"), "card": 3,
     "filtered": True, "params": (0.0, 0.0, 1.0, 0.0, 1.0),
     "aggs": ("mean", "min"), "max_groups": 32},
    # heavy invalidity + tiny table still above the sort-free floor
    {"seed": 9, "n": 136, "key_dtypes": ("int32",), "card": 2,
     "invalid_frac": 0.6, "aggs": ("prod", "sum", "argmax"),
     "max_groups": 8},
    # NaN keys and no bound: the fresh call sorts, and must still group
    # equal NaN keys as one group, like the hash-slotted route
    {"seed": 0, "n": 136, "key_dtypes": ("float32",), "card": 2,
     "nan_keys": True, "aggs": ("sum",)},
]
