"""Structural jaxpr spies: assert properties of a traced program that
timing cannot (and unit values will not) catch.

The first client is the fused arg-extremum acceptance bound: the grouped
argmin/argmax lowering must issue NO row-capacity-sized gather — the
kernel's index moment replaced the ``take(best, seg)`` hit-detection scan
and the full-row candidate reduce, and the jnp fallback computes the index
with a segmented ``associative_scan`` (slices, not gathers).  The spy
compares against a no-arg baseline program rather than demanding zero,
so the rest of the grouped program's row traffic is not its concern: the
arg-extremum must add nothing row-sized (``benchmarks/arg_gather_spy.py``,
a tier-1 test, and a dedicated CI step all assert it).

The second client is the SORT census of the sort-free grouped route
(hash-slotted segment ids, relational/keyslot.py): its acceptance bound
is that the traced program contains ZERO row-capacity-sized ``sort``
equations — the group sort, its per-key argsorts, and ``compress`` all
lower to the ``sort`` primitive, so ``count_row_sized_sorts`` pins "the
sort stays deleted" structurally (``benchmarks/sortfree_spy.py``, a
tier-1 test, and a CI step).

The third client is the sorted route's group-key table
(``engine.segment_keys``): one compaction sort replaced a scatter of
one update per row, and ``count_row_sized_scatters`` pins "the
scatter stays deleted" on one chip and shard by shard
(``tests/test_segment_keys.py``).

Counting is done on the CLOSED jaxpr, pre-optimization: every ``jnp.take``
/ advanced-index lowers to the ``gather`` primitive there, every
``jnp.argsort`` / ``lax.sort`` to the ``sort`` primitive, the counts are
deterministic (no backend fusion heuristics), and sub-jaxprs — jit calls,
scan bodies, while bodies, shard_map bodies, and interpret-mode
``pallas_call`` kernels — are walked recursively, so nothing hides inside
a call boundary.
"""
from __future__ import annotations

import math
from typing import Iterator

from jax.extend import core as _core


def _sub_jaxprs(params) -> Iterator["_core.Jaxpr"]:
    for v in params.values():
        yield from _as_jaxprs(v)


def _as_jaxprs(v) -> Iterator["_core.Jaxpr"]:
    if isinstance(v, _core.ClosedJaxpr):
        yield v.jaxpr
    elif isinstance(v, _core.Jaxpr):
        yield v
    elif isinstance(v, (tuple, list)):
        for x in v:
            yield from _as_jaxprs(x)


def iter_eqns(jaxpr) -> Iterator:
    """Every equation of ``jaxpr`` and, recursively, of every sub-jaxpr
    carried in equation params (pjit, scan, while, shard_map, pallas_call,
    custom_* wrappers, ...)."""
    if isinstance(jaxpr, _core.ClosedJaxpr):
        jaxpr = jaxpr.jaxpr
    for eqn in jaxpr.eqns:
        yield eqn
        for sub in _sub_jaxprs(eqn.params):
            yield from iter_eqns(sub)


def gather_output_sizes(jaxpr) -> list[int]:
    """Flattened output element count of every ``gather`` equation in the
    (closed) jaxpr, recursing through call boundaries."""
    sizes = []
    for eqn in iter_eqns(jaxpr):
        if eqn.primitive.name == "gather":
            shape = getattr(eqn.outvars[0].aval, "shape", ())
            sizes.append(int(math.prod(shape)))
    return sizes


def sort_output_sizes(jaxpr) -> list[int]:
    """Largest flattened output element count of every ``sort`` equation
    in the (closed) jaxpr, recursing through call boundaries.  A variadic
    sort (``lax.sort`` with several operands, e.g. ``Table.sort_by``'s
    keys + carried columns) is ONE equation — its widest output is the
    size that matters, and fusing K argsorts into one variadic sort is
    visible as K equations collapsing to one."""
    sizes = []
    for eqn in iter_eqns(jaxpr):
        if eqn.primitive.name == "sort":
            sizes.append(max(
                int(math.prod(getattr(v.aval, "shape", ())))
                for v in eqn.outvars))
    return sizes


def count_row_sized_sorts(jaxpr, n: int) -> int:
    """Number of sort equations whose output is at least row-set-sized —
    the acceptance metric of the sort-free grouped route: hash-slotted
    segment assignment must leave ZERO of these in the traced program
    (segment-sized sorts, should any appear, are legal — O(num_segments)
    work was never the problem)."""
    return sum(1 for s in sort_output_sizes(jaxpr) if s >= n)


def count_row_sized_gathers(jaxpr, n: int) -> int:
    """Number of gather equations whose OUTPUT is at least row-set-sized.

    This is the acceptance metric of the fused arg-extremum path: a
    ``take(best, seg)`` hit-detection scan materializes an (N,)-sized
    gather output, while the index-moment lowering's payload take outputs
    only (num_segments,) elements.  Gathers *reading* a row-sized operand
    but emitting a segment-sized result are intentionally not counted —
    output size is what the collective/memory cost scales with."""
    return sum(1 for s in gather_output_sizes(jaxpr) if s >= n)


def scatter_update_sizes(jaxpr) -> list[int]:
    """Flattened element count of the UPDATES operand of every scatter
    equation (``scatter``, ``scatter-add``, ``scatter-min``, ...) in the
    (closed) jaxpr, recursing through call boundaries."""
    sizes = []
    for eqn in iter_eqns(jaxpr):
        if eqn.primitive.name.startswith("scatter"):
            shape = getattr(eqn.invars[2].aval, "shape", ())
            sizes.append(int(math.prod(shape)))
    return sizes


def count_row_sized_scatters(jaxpr, n: int) -> int:
    """Number of scatter equations that take at least one update per row
    of an ``n``-row set — the acceptance metric of the sorted route's
    group-key table (``engine.segment_keys``): a compaction by one sort
    leaves ZERO of these, where a ``segment_min`` of each segment's first
    row, or a ``.at[seg].max`` of its key, takes one update per row (on
    a TPU v5e about 12 ns a row, against 1-1.5 ns for a sort payload).
    Scatters of segment-sized updates are not counted."""
    return sum(1 for s in scatter_update_sizes(jaxpr) if s >= n)


def row_census(jaxpr, n: int) -> dict[str, int]:
    """Row-sized sort AND gather counts in one walk — the combined
    acceptance census of the whole-plan-fusion clients: the hash-join /
    fused-chain lowering must show zero row-sized sorts (the legacy
    join's stable argsort, ``compress``'s permutation sort, and the
    group sort all register here) and no more row-sized gathers than the
    materialized plan it replaced.  ``Limit`` is covered by the same
    counters: its old ``compress()`` lowering costs one row-sized sort
    plus per-column row-sized gathers, while the prefix-sum rewrite
    (engine) is a cumsum + compare — nothing registers."""
    return {"sorts": count_row_sized_sorts(jaxpr, n),
            "gathers": count_row_sized_gathers(jaxpr, n)}
