"""Comparison helpers shared by the plain references."""
from __future__ import annotations

import ml_dtypes
import numpy as np

bf16 = ml_dtypes.bfloat16


def positions(domain, keys) -> np.ndarray:
    """Position of each key in ``domain`` (sorted ascending, unique), -1
    for a key that is not in it."""
    domain = np.asarray(domain)
    keys = np.asarray(keys)
    i = np.clip(np.searchsorted(domain, keys), 0, max(len(domain) - 1, 0))
    return np.where(domain[i] == keys, i, -1) if len(domain) else \
        np.full(keys.shape, -1)


def bad_groups(keys, vals, domain, present, ref) -> int:
    """Groups that are wrong in a served result over a key domain
    (``present`` and ``ref`` indexed by position in ``domain``): keys
    served twice, keys missing or extra against ``present``, and values
    that differ from ``ref`` (exact)."""
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


def bf16_group_sum(keys, w, n: int) -> np.ndarray:
    """Per-key sums of ``w`` with the inputs rounded to bfloat16 and each
    key's sum accumulated in bfloat16, in row order."""
    keys = np.asarray(keys)
    order = np.argsort(keys, kind="stable")
    ks = keys[order]
    vals = np.asarray(w)[order].astype(np.float32).astype(bf16)
    out = np.zeros(n, bf16)
    if len(ks) == 0:
        return out
    starts = np.flatnonzero(np.r_[True, ks[1:] != ks[:-1]])
    out[ks[starts]] = np.add.reduceat(vals, starts, dtype=bf16)
    return out
