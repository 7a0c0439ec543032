"""The plain reference of ``chipbench/reference/tpch_loops.py`` with key
lookups in linear time, for inputs the size of the SF 30 cell.

The answers, the numbers compared and the control are those of
``tpch_loops``: the loops' semantics do not change with the layout.  Only
two steps are computed another way, each giving the same result:

* the position of each key in a sorted domain of integer keys comes from
  a table indexed by key (the domain's span is at most a few times its
  length: dense supplier keys, order keys that use 8 of every 32), where
  ``common.positions`` runs a binary search per key — 180M of them for
  LINEITEM at SF 30;
* an answer whose keys are exactly the domain's keys that have input
  rows, in domain order (the sorted route's answers), is compared
  element by element with the reference's answer, made once per loop
  and parameters; any other answer goes through ``common.bad_groups``'s
  general count of keys served twice, missing or extra, and values that
  differ.
"""
from __future__ import annotations

import numpy as np

from chipbench.reference import common, tpch_loops

#: a domain whose key span exceeds this many times its length falls back
#: to the binary search
MAX_SPAN = 8


def positions(domain, keys) -> np.ndarray:
    """``common.positions``: each key's position in ``domain`` (sorted
    ascending, unique), -1 for a key that is not in it."""
    domain, keys = np.asarray(domain), np.asarray(keys)
    if (not len(domain) or domain.dtype.kind not in "iu"
            or keys.dtype.kind not in "iu"):
        return common.positions(domain, keys)
    lo, hi = int(domain[0]), int(domain[-1])
    span = hi - lo + 1
    if span > MAX_SPAN * len(domain):
        return common.positions(domain, keys)
    # int32 where every offset fits, for the memory traffic
    it = np.int32 if max(span, len(domain), abs(lo)) < 1 << 30 \
        and keys.dtype.itemsize <= 4 else np.int64
    table = np.full(span, -1, it)
    table[domain.astype(it) - it(lo)] = np.arange(len(domain), dtype=it)
    k = keys.astype(it) - it(lo)
    inside = (k >= 0) & (k < span)
    return np.where(inside, table[np.where(inside, k, 0)], -1)


def bad_groups(keys, vals, domain, present, ref) -> int:
    """``common.bad_groups`` with the lookups above."""
    pos = positions(domain, keys)
    vals = np.asarray(vals)
    n = len(present)
    inside = pos >= 0
    seen = np.bincount(pos[inside], minlength=n)
    dup = int(np.sum(seen > 1))
    extra = int(np.sum(~inside)) + int(np.sum((seen > 0) & ~present))
    missing = int(np.sum(present & (seen == 0)))
    k = pos[inside]
    ok = present[k]
    differ = int(np.sum(vals[inside][ok].astype(np.float64)
                        != np.asarray(ref)[k[ok]].astype(np.float64)))
    return dup + extra + missing + differ


class Reference(tpch_loops.Reference):
    """``tpch_loops.Reference`` with the lookups above."""

    def positions(self, name):
        if name not in self._pos:
            dom = self.domain(name)
            if np.any(np.diff(dom) <= 0):
                raise ValueError(f"{name}: domain keys are not ascending")
            t, c = tpch_loops.CORRELATED[name]
            pos = positions(dom, self.h[t][c])
            if np.any(pos < 0):
                raise ValueError(f"{name}: an input key is not in {t}")
            self._pos[name] = pos
        return self._pos[name]

    def check(self, name, params, got: dict) -> dict:
        key, val = self.cols(name)
        want = self.answer(name, params)
        if np.array_equal(got[key], want[key]):
            # the domain's present keys, in order: no key served twice,
            # missing or extra, so only values can differ
            return {"bad_groups": int(np.count_nonzero(
                np.asarray(got[val]).astype(np.float64) != want[val]))}
        present, ref = self.want(name, params)
        return {"bad_groups": bad_groups(got[key], got[val],
                                         self.domain(name), present, ref)}

    def answer(self, name, params, control=False) -> dict:
        """``tpch_loops.Reference.answer``, computed once per loop and
        parameters (values in float64, as the comparison reads them)."""
        k = ("answer", name, tuple(sorted((p, float(v))
                                          for p, v in params.items())),
             control)
        if k not in self._memo:
            key, val = self.cols(name)
            a = super().answer(name, params, control)
            self._memo[k] = {key: a[key],
                             val: np.asarray(a[val]).astype(np.float64)}
        return self._memo[k]
