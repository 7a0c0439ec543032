"""Plain numpy reference of the grouped TPC-H cursor loops.

Group-bys over host copies of the benchmark's own data, written from the
loops' semantics; nothing here imports the system under test.  Each
returns, per key of the loop's correlation domain (in key order), whether
the key has input rows and the loop's returned value for it.

``control=True`` computes the same in bfloat16, the precision below the
float32 the configuration states: inputs rounded to bfloat16 and every
sum accumulated in bfloat16.  Put in the program's place, it must fail
the comparison.
"""
from __future__ import annotations

import numpy as np

from chipbench.reference.common import (bad_groups, bf16_group_sum, bf16,
                                        positions)

#: the correlation domain of each loop: (table, key column)
DOMAIN = {"Q2": ("PART", "p_partkey"), "Q13": ("CUSTOMER", "c_custkey"),
          "Q18": ("ORDERS", "o_orderkey"), "Q21": ("SUPPLIER", "s_suppkey")}
#: the input column each loop is correlated on: (table, column)
CORRELATED = {"Q2": ("PARTSUPP", "ps_partkey"),
              "Q13": ("ORDERS", "o_custkey"),
              "Q18": ("LINEITEM", "l_orderkey"),
              "Q21": ("LINEITEM", "l_suppkey")}


def grouped(q: str, h: dict, params: dict, pos, control: bool = False):
    """(present[i], value[i]) for one grouped loop over the positions of
    its domain; ``pos`` is each input row's domain position."""
    n = len(h[DOMAIN[q][0]][DOMAIN[q][1]])
    present = np.bincount(pos, minlength=n) > 0
    if q == "Q2":
        return present, _q2(h, params, pos, n, control)
    if q == "Q13":
        w = ~h["ORDERS"]["o_comment_special"]
    elif q == "Q18":
        w = h["LINEITEM"]["l_quantity"]
    elif q == "Q21":
        li = h["LINEITEM"]
        w = li["l_receiptdate"] > li["l_commitdate"]
    else:
        raise KeyError(q)
    if control:
        return present, bf16_group_sum(pos, w, n).astype(np.float64)
    return present, np.bincount(pos, weights=w, minlength=n)


def _q2(h, params, part, n, control):
    # per part: the supplier name of the first row, in scan order, whose
    # cost is the least among those above the lower bound and below the
    # loop's initial minCost; -1 when no row qualifies
    ps, s = h["PARTSUPP"], h["SUPPLIER"]
    cost = ps["ps_supplycost"]
    lb, top = np.float32(params["lb"]), np.float32(params["minCost"])
    if control:
        cost, lb, top = (np.asarray(x).astype(bf16) for x in (cost, lb, top))
    idx = np.flatnonzero((cost > lb) & (cost < top))
    order = idx[np.lexsort((idx, cost[idx].astype(np.float64), part[idx]))]
    first_part, first = np.unique(part[order], return_index=True)
    name = np.full(n, -1, np.int64)
    supp = positions(s["s_suppkey"], ps["ps_suppkey"][order[first]])
    if np.any(supp < 0):
        raise ValueError("Q2: a PARTSUPP row names no supplier")
    name[first_part] = s["s_name"][supp]
    return name


class Reference:
    """Compares served loop results with the numpy reference; one
    reference per (loop, parameters), computed once."""

    def __init__(self, host: dict, result_columns):
        self.h = host
        self.cols = result_columns
        self._memo = {}
        self._pos = {}

    def domain(self, name):
        t, c = DOMAIN[name]
        return self.h[t][c]

    def positions(self, name):
        """Each input row's position in the loop's domain, computed
        once per loop."""
        if name not in self._pos:
            dom = self.domain(name)
            if np.any(np.diff(dom) <= 0):
                raise ValueError(f"{name}: domain keys are not ascending")
            t, c = CORRELATED[name]
            pos = positions(dom, self.h[t][c])
            if np.any(pos < 0):
                raise ValueError(f"{name}: an input key is not in {t}")
            self._pos[name] = pos
        return self._pos[name]

    def want(self, name, params, control=False):
        key = (name, tuple(sorted((k, float(v)) for k, v in params.items())),
               control)
        if key not in self._memo:
            p = {k: float(v) for k, v in params.items()}
            self._memo[key] = grouped(name, self.h, p, self.positions(name),
                                      control)
        return self._memo[key]

    def check(self, name, params, got: dict) -> dict:
        """Numbers compared for one served result (``got``: column →
        values of its valid rows)."""
        key, val = self.cols(name)
        present, ref = self.want(name, params)
        return {"bad_groups": bad_groups(got[key], got[val],
                                         self.domain(name), present, ref)}

    def answer(self, name, params, control=False) -> dict:
        """The reference's answer (the control's, with ``control``) in
        the served result's layout."""
        key, val = self.cols(name)
        present, ref = self.want(name, params, control)
        return {key: self.domain(name)[present], val: ref[present]}
