"""The TPC-H cursor loops of the Aggify paper (SIGMOD 2020, section 10.1),
served in their grouped (Aggify+) form.

The loop programs are copies of the system's TPC-H workload, kept here so
that a change there cannot move the benchmark.  ``build`` aggifies each
loop with the system's own ``aggify``, strips the correlation filter and
groups by the correlation column, with ``max_groups`` set to the
correlation domain: the call the server is given.
"""
from __future__ import annotations

import numpy as np

from chipbench.data.tpch import SCHEMAS

#: correlation domain of each grouped loop: the table and key column the
#: loop is invoked per; its row count bounds the Aggify+ group count
DOMAIN = {"Q2": ("PART", "p_partkey"), "Q13": ("CUSTOMER", "c_custkey"),
          "Q18": ("ORDERS", "o_orderkey"), "Q21": ("SUPPLIER", "s_suppkey")}

#: catalog tables each call reads (its input rows)
SCANS = {"Q2": ("PARTSUPP", "SUPPLIER"), "Q13": ("ORDERS",),
         "Q18": ("LINEITEM",), "Q21": ("LINEITEM",)}

#: columns each call reads from its input, for the bytes it needs
READS = {"Q2": {"PARTSUPP": ("ps_partkey", "ps_suppkey", "ps_supplycost"),
                "SUPPLIER": ("s_suppkey", "s_name")},
         "Q13": {"ORDERS": ("o_custkey", "o_comment_special")},
         "Q18": {"LINEITEM": ("l_orderkey", "l_quantity")},
         "Q21": {"LINEITEM": ("l_suppkey", "l_receiptdate",
                              "l_commitdate")}}

#: result columns: (group key, returned loop variable)
RESULT = {"Q2": ("ps_partkey", "suppName"), "Q13": ("o_custkey", "cnt"),
          "Q18": ("l_orderkey", "qty"), "Q21": ("l_suppkey", "late")}

#: the loops' scalar parameters and their defaults
DEFAULT_PARAMS = {"Q2": {"lb": 4.0}, "Q13": {}, "Q18": {}, "Q21": {}}


def _scan(t):
    from repro.relational import Scan
    return Scan(t, SCHEMAS[t])


def q2_min_cost_supp():
    """Per-part minimum-cost supplier above a lower bound (Figure 1)."""
    import jax.numpy as jnp
    from repro.core import (Assign, BinOp, Col, Const, CursorLoop, If,
                            Program, Var, let)
    from repro.relational import Filter, Join
    q = Filter(Join(_scan("PARTSUPP"), _scan("SUPPLIER"),
                    left_key="ps_suppkey", right_key="s_suppkey"),
               Col("ps_partkey").eq(Var("pkey")))
    body = [If(BinOp("and", Var("pCost") < Var("minCost"),
                     Var("pCost") > Var("lb")),
               [Assign("minCost", Var("pCost")),
                Assign("suppName", Var("sName"))])]
    return Program(
        "minCostSupp", params=("pkey", "lb"),
        pre=[let("minCost", Const(100000.0)), let("suppName", Const(-1))],
        loop=CursorLoop(q, fetch=[("pCost", "ps_supplycost"),
                                  ("sName", "s_name")], body=body),
        post=[], returns=("suppName",),
        var_dtypes={"suppName": jnp.int32})


def q13_order_count():
    """Per-customer count of orders without special requests."""
    from repro.core import (Assign, Col, Const, CursorLoop, If, Program,
                            UnOp, Var, let)
    from repro.relational import Filter
    q = Filter(_scan("ORDERS"), Col("o_custkey").eq(Var("ck")))
    body = [If(UnOp("not", Var("special")),
               [Assign("cnt", Var("cnt") + 1.0)])]
    return Program(
        "orderCount", params=("ck",), pre=[let("cnt", Const(0.0))],
        loop=CursorLoop(q, fetch=[("special", "o_comment_special")],
                        body=body),
        post=[], returns=("cnt",))


def q18_order_quantity():
    """Per-order total quantity (large-volume customers)."""
    from repro.core import Assign, Col, Const, CursorLoop, Program, Var, let
    from repro.relational import Filter
    q = Filter(_scan("LINEITEM"), Col("l_orderkey").eq(Var("ok")))
    return Program(
        "orderQty", params=("ok",), pre=[let("qty", Const(0.0))],
        loop=CursorLoop(q, fetch=[("lq", "l_quantity")],
                        body=[Assign("qty", Var("qty") + Var("lq"))]),
        post=[], returns=("qty",))


def q21_waiting_suppliers():
    """Per-supplier count of line items received after their commit."""
    from repro.core import (Assign, Col, Const, CursorLoop, If, Program,
                            Var, let)
    from repro.relational import Filter
    q = Filter(_scan("LINEITEM"), Col("l_suppkey").eq(Var("sk")))
    body = [If(Var("rd") > Var("cd"), [Assign("late", Var("late") + 1.0)])]
    return Program(
        "lateCount", params=("sk",), pre=[let("late", Const(0.0))],
        loop=CursorLoop(q, fetch=[("rd", "l_receiptdate"),
                                  ("cd", "l_commitdate")], body=body),
        post=[], returns=("late",))


#: loop program factory, correlation parameter, Aggify+ group key
QUERIES = {"Q2": (q2_min_cost_supp, "pkey", "ps_partkey"),
           "Q13": (q13_order_count, "ck", "o_custkey"),
           "Q18": (q18_order_quantity, "ok", "l_orderkey"),
           "Q21": (q21_waiting_suppliers, "sk", "l_suppkey")}


def build(catalog, names) -> dict:
    """name → (plan, params) for the grouped loops in ``names``:
    ``params`` are the loop's scalar parameters plus the pre-loop state of
    the aggregate's fields, as host scalars in the dtypes JAX gives
    them."""
    import jax.numpy as jnp
    from repro.core import aggify
    from repro.core.executors import build_env
    from repro.relational import AggCall, Filter

    out = {}
    for q in names:
        factory, corr, gk = QUERIES[q]
        prog = factory()
        call = aggify(prog).agg_call
        if not isinstance(call.child, Filter):
            raise ValueError(f"{q}: aggified loop has no correlation filter")
        base = dict(DEFAULT_PARAMS[q])
        env = build_env(prog, catalog, {**base, corr: 0})
        params = {k: np.asarray(jnp.asarray(v)) for k, v in base.items()}
        params.update({f: np.asarray(jnp.asarray(env[f]))
                       for f in call.aggregate.fields if f in env})
        plan = AggCall(call.child.child, call.aggregate, call.param_binding,
                       call.ordered, call.sort_keys, call.sort_desc,
                       group_keys=(gk,),
                       max_groups=catalog[DOMAIN[q][0]].capacity)
        out[q] = (plan, params)
    return out


def scans(name: str) -> tuple:
    return SCANS[name]


def reads(name: str) -> dict:
    return READS[name]


def result_columns(name: str) -> tuple:
    return RESULT[name]


def domain(name: str) -> tuple:
    """(table, key column) of the loop's correlation domain."""
    return DOMAIN[name]


def groups(name: str, n: dict) -> int:
    """Groups in the result: the correlation domain's rows that have
    input rows (TPC-H gives no orders to a customer whose key is a
    multiple of 3)."""
    table = DOMAIN[name][0]
    return n[table] - (n[table] // 3 if name == "Q13" else 0)
