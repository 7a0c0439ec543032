"""The sorted route's group-key table (``relational/engine.py``
``segment_keys``): one compaction sort of each group's first row, in
place of a ``segment_min`` of the first row's index and a gather of the
keys by it.

* The helper equals that scatter formulation bit for bit, on every entry
  of the table (groups and the entries past them), on int, float (NaN,
  -0.0, ±inf), bool and two-column keys, with fewer groups than the
  table, a table wider than the rows, more groups than the table, no
  valid row, and no row at all.
* The one-chip sorted route (a grouped cursor loop and a ``GroupAgg``)
  still answers the TPC-H loops exactly, against numpy.
* The lowered sorted route takes no scatter of one update per row: on
  one chip and shard by shard, on the Q18 and Q21 shapes.  On a TPU v5e
  such a scatter costs about 12 ns a row, a sort payload 1-1.5 ns.
"""
import numpy as np
import pytest

import jax
import jax.numpy as jnp
from jax.sharding import AbstractMesh

from repro.analysis.jaxpr_spy import count_row_sized_scatters
from repro.core import aggify
from repro.core.executors import build_env
from repro.relational import Table, execute
from repro.relational.engine import (grouped_route, segment_ids_for,
                                     segment_keys)
from repro.relational.plan import AggCall, GroupAgg, Scan
from repro.relational.tpch import gen_tpch


def scatter_keys(st, starts, seg, keys, width):
    """The formulation ``segment_keys`` replaced: each segment's first
    row by a ``segment_min`` over the rows, its keys by a gather."""
    cap = st.capacity
    first = jax.ops.segment_min(jnp.where(starts, jnp.arange(cap), cap),
                                seg, num_segments=width)
    safe = jnp.clip(first, 0, cap - 1)
    return {k: jnp.take(st.columns[k], safe) for k in keys}


def _bits(a):
    a = np.asarray(a)
    return a if a.dtype == np.bool_ else a.view(f"u{a.dtype.itemsize}")


def _table(rng, n, kinds, invalid=0.2):
    cols = {}
    for i, kind in enumerate(kinds):
        if kind == "int":
            c = rng.integers(-40, 40, n).astype(np.int32)
        elif kind == "float":
            pool = np.array([np.nan, -0.0, 0.0, np.inf, -np.inf, 1.5, -2.25,
                             3.0], np.float32)
            c = pool[rng.integers(0, len(pool), n)]
        else:
            c = rng.random(n) < 0.5
        cols[f"k{i}"] = c
    cols["v"] = rng.random(n).astype(np.float32)
    return Table.from_columns(**cols).filter(
        jnp.asarray(rng.random(n) >= invalid)) if n else \
        Table({k: jnp.asarray(c) for k, c in cols.items()},
              jnp.zeros((0,), bool))


#: case → (rows, key kinds, table width, share of invalid rows)
CASES = {
    "int": (600, ("int",), 129, 0.2),
    "float_nan_zero_inf": (600, ("float",), 17, 0.2),
    "bool": (300, ("bool",), 5, 0.2),
    "two_columns": (900, ("int", "float"), 513, 0.2),
    "fewer_groups_than_width": (400, ("int",), 1025, 0.2),
    "width_past_rows": (50, ("int",), 257, 0.2),
    "more_groups_than_width": (700, ("int",), 33, 0.2),
    "no_valid_row": (200, ("int", "float"), 65, 1.0),
    "empty_table": (0, ("int",), 9, 0.0),
}


@pytest.mark.parametrize("case", list(CASES))
def test_segment_keys_matches_the_scatter_it_replaced(case):
    n, kinds, width, invalid = CASES[case]
    t = _table(np.random.default_rng(len(case) * 7 + n), n, kinds, invalid)
    keys = tuple(f"k{i}" for i in range(len(kinds)))
    st, seg, starts = segment_ids_for(t, keys, num_segments=width)
    got = segment_keys(st, starts, seg, keys, width)
    jitted = jax.jit(segment_keys, static_argnums=(3, 4))(st, starts, seg,
                                                         keys, width)
    if n:
        want = scatter_keys(st, starts, seg, keys, width)
    else:
        # no row to read: the scatter's gather cannot run on an empty
        # axis, and the compaction returns zeros
        want = {k: jnp.zeros((width,), t.columns[k].dtype) for k in keys}
    for k in keys:
        assert got[k].shape == (width,) and got[k].dtype == want[k].dtype
        np.testing.assert_array_equal(_bits(got[k]), _bits(want[k]))
        np.testing.assert_array_equal(_bits(jitted[k]), _bits(want[k]))


# --- the one-chip sorted route on TPC-H-shaped tables -----------------------

#: query → (group key, correlation domain table)
LOOPS = {"Q13": ("o_custkey", "CUSTOMER"), "Q18": ("l_orderkey", "ORDERS"),
         "Q21": ("l_suppkey", "SUPPLIER")}


def _loop_plan(q, max_groups):
    """The TPC-H loop ``q`` aggified and grouped by its correlation
    column, as the server is given it."""
    from benchmarks.queries import DEFAULT_PARAMS, QUERIES
    factory, corr, gk = QUERIES[q]
    prog = factory()
    call = aggify(prog).agg_call
    env = build_env(prog, {}, {**DEFAULT_PARAMS[q], corr: 0})
    env = {f: env[f] for f in call.aggregate.fields if f in env}
    return AggCall(call.child.child, call.aggregate, call.param_binding,
                   call.ordered, call.sort_keys, call.sort_desc,
                   group_keys=(gk,), max_groups=max_groups), env


def _reference(q, plan, rows):
    """Per group key in key order, in numpy: the loop's returned value,
    or the ``GroupAgg``'s sum of ``l_quantity`` (Q18) or count."""
    k = rows[LOOPS[q][0]]
    if plan == "group_agg":
        v = rows["l_quantity"] if q == "Q18" else np.ones(len(k))
    elif q == "Q13":
        v = ~rows["o_comment_special"]
    elif q == "Q18":
        v = rows["l_quantity"]
    else:
        v = rows["l_receiptdate"] > rows["l_commitdate"]
    uk, inv = np.unique(k, return_inverse=True)
    return uk, np.bincount(inv.reshape(-1), v.astype(np.float64), len(uk))


@pytest.mark.parametrize("plan", ["loop", "group_agg"])
@pytest.mark.parametrize("q", list(LOOPS))
def test_one_chip_sorted_route_answers_the_tpch_loops(q, plan, monkeypatch):
    monkeypatch.setenv("REPRO_GROUPAGG_SORTFREE", "off")
    cat = gen_tpch(0.001, seed=17)
    key, dom = LOOPS[q]
    tab = "ORDERS" if q == "Q13" else "LINEITEM"
    if plan == "loop":
        p, env = _loop_plan(q, cat[dom].capacity)
        out = p.aggregate.terminate_vars[0]
    else:
        agg = ("r", "sum", "l_quantity") if q == "Q18" \
            else ("r", "count", None)
        p, env = GroupAgg(Scan(tab, cat[tab].columns), (key,), (agg,),
                          max_groups=cat[dom].capacity), None
        out = "r"
    route = grouped_route(p, cat[tab], env)
    assert not route.sortfree and route.merge is None
    uk, ref = _reference(q, plan, cat[tab].to_numpy())
    for how, res in (("eager", execute(p, cat, env)),
                     ("jit", jax.jit(lambda c, e: execute(p, c, e))(cat,
                                                                   env))):
        got = res.to_numpy()
        np.testing.assert_array_equal(got[key], uk, err_msg=how)
        np.testing.assert_array_equal(np.asarray(got[out], np.float64), ref,
                                      err_msg=how)


# --- census: no scatter of one update per row -------------------------------

LINEITEM_COLS = (("l_orderkey", jnp.int32), ("l_suppkey", jnp.int32),
                 ("l_quantity", jnp.float32), ("l_receiptdate", jnp.int32),
                 ("l_commitdate", jnp.int32))

#: query → (group key, max_groups as a share of the rows)
SHAPES = {"Q18": ("l_orderkey", 4), "Q21": ("l_suppkey", 160)}


@pytest.mark.parametrize("plan", ["loop", "group_agg"])
@pytest.mark.parametrize("q", list(SHAPES))
@pytest.mark.parametrize("where", ["one_chip", "shard_local"])
def test_sorted_route_takes_no_row_sized_scatter(where, q, plan,
                                                 monkeypatch):
    """The lowered sorted route, with the chip's kernel in place of the
    jnp fallback (whose aggregate is itself a scatter of the rows):
    ``n`` rows on one chip, or four shards of ``n / 4`` rows each
    (traced over an abstract four-device mesh, split by order-key
    ranges as the four-chip cell's LINEITEM is)."""
    monkeypatch.setenv("REPRO_GROUPAGG_SORTFREE", "off")
    monkeypatch.setenv("REPRO_GROUPAGG_FUSED", "interpret")
    monkeypatch.setenv("REPRO_SEGAGG_BACKEND", "interpret")
    n = 1 << 14
    key, share = SHAPES[q]
    split = None
    rows = n
    if where == "shard_local":
        split = (AbstractMesh((4,), ("data",)), "data", ("l_orderkey",))
        rows = n // 4
    t = Table({c: jax.ShapeDtypeStruct((n,), d) for c, d in LINEITEM_COLS},
              jax.ShapeDtypeStruct((n,), jnp.bool_), row_split=split)
    if plan == "loop":
        p, env = _loop_plan(q, n // share)
    else:
        p, env = GroupAgg(Scan("LINEITEM", tuple(c for c, _ in
                                                  LINEITEM_COLS)),
                          (key,), (("r", "sum", "l_quantity"),),
                          max_groups=n // share), None
    route = grouped_route(p, t, env)
    assert not route.sortfree
    assert route.merge == (None if where == "one_chip"
                           else {"Q18": "ranges", "Q21": "keys"}[q])
    jaxpr = jax.make_jaxpr(lambda c, e: execute(p, c, e).columns)(
        {"LINEITEM": t}, env)
    assert count_row_sized_scatters(jaxpr, rows) == 0
