"""Structural CI gate: the sort-free grouped lowering contains ZERO
row-capacity-sized sort ops — and no new row-sized gathers.

The sort-free route (relational/keyslot.py hash-slotted segment ids +
``layout='unsorted'`` kernel accumulation) exists to delete the group
sort — one stable multi-key ``lax.sort`` that carries every column —
from the grouped hot path.  This spy pins that deletion on the *traced
program*, where it cannot silently regress:

1. **Sort census** — the bench-shape grouped programs (built-in
   ``GroupAgg`` over every op class incl. argmin, and the fused grouped
   ``AggCall`` workloads) trace to ZERO sort equations with row-sized
   output under the sort-free route.  Segment-sized sorts would be legal
   (O(num_segments) work was never the problem); there are none of those
   either today, but only row scale is gated.
2. **Gather census** — the same programs trace to NO MORE row-sized
   gathers than their sorted-route twins, so the route never trades the
   sort for equivalent gather traffic.  The sorted twins gather no rows
   (``Table.sort_by`` carries every column through its sort), so the
   slotting probe loop's owner/key lookups must gather none either.
3. **Detector sanity** — the SAME programs with the route disabled
   (``REPRO_GROUPAGG_SORTFREE=off``) trace to at least one row-sized
   sort, proving the census would catch a regression to the sorted
   lowering.

Run as a module (the CI step) or import the helpers from tests:

    PYTHONPATH=src python -m benchmarks.sortfree_spy
"""
from __future__ import annotations

import sys

import jax

from repro.analysis.jaxpr_spy import (count_row_sized_gathers,
                                      count_row_sized_sorts)
from repro.relational import execute

#: the GroupAgg op battery the census traces (argmin included: its
#: unsorted jnp arg pick costs one hit-detection gather, which must stay
#: within the sorted route's own gather budget)
GROUPAGG_AGGS = (("s", "sum", "ps_supplycost"), ("c", "count", None),
                 ("mn", "min", "ps_supplycost"),
                 ("mx", "max", "ps_supplycost"),
                 ("avg", "mean", "ps_supplycost"),
                 ("am", "argmin", ("ps_supplycost", "ps_suppkey")))


def _with_env(sortfree: bool, backend: str, fn):
    from benchmarks.util import pin_env
    with pin_env(REPRO_GROUPAGG_SORTFREE="on" if sortfree else "off",
                 REPRO_SEGAGG_BACKEND=backend,
                 REPRO_GROUPAGG_FUSED=backend):
        return fn()


def trace_groupagg(n: int, ngroups: int, sortfree: bool,
                   backend: str = "jnp"):
    """Closed jaxpr of the bench-shape built-in GroupAgg (dense bound
    declared — the sort-free dispatch condition) under either route."""
    from benchmarks.group_agg import _catalog
    from repro.relational.plan import GroupAgg, Scan
    cat = _catalog(n, ngroups)
    plan = GroupAgg(Scan("PARTSUPP",
                         ("ps_partkey", "ps_suppkey", "ps_supplycost")),
                    ("ps_partkey",), GROUPAGG_AGGS, max_groups=ngroups)

    def run():
        t = execute(plan, cat)
        return tuple(t.columns.values()) + (t.valid,)

    return _with_env(sortfree, backend, lambda: jax.make_jaxpr(run)())


def trace_agg_call(prog, env, cat, sortfree: bool, max_groups: int,
                   backend: str = "jnp"):
    """Closed jaxpr of a fused grouped AggCall under either route."""
    from repro.core import aggify
    from repro.relational.plan import AggCall
    rp = aggify(prog)
    call = AggCall(rp.agg_call.child, rp.agg_call.aggregate,
                   rp.agg_call.param_binding, rp.agg_call.ordered,
                   rp.agg_call.sort_keys, rp.agg_call.sort_desc,
                   group_keys=("ps_partkey",), mode="fused",
                   max_groups=max_groups)

    def run():
        t = execute(call, cat, env)
        return tuple(t.columns.values()) + (t.valid,)

    return _with_env(sortfree, backend, lambda: jax.make_jaxpr(run)())


def sortfree_census(n: int = 50_000, ngroups: int = 512,
                    backend: str = "jnp") -> dict[str, dict[str, int]]:
    """{program: {row_sorts_sortfree, row_sorts_sorted,
    row_gathers_sortfree, row_gathers_sorted}} over the built-in
    GroupAgg battery and every fused grouped AggCall bench workload."""
    from benchmarks.group_agg import _catalog, _programs
    cat = _catalog(n, ngroups)
    out: dict[str, dict[str, int]] = {}

    def census(name, tracer):
        free, sorted_ = tracer(True), tracer(False)
        out[name] = {
            "row_sorts_sortfree": count_row_sized_sorts(free, n),
            "row_sorts_sorted": count_row_sized_sorts(sorted_, n),
            "row_gathers_sortfree": count_row_sized_gathers(free, n),
            "row_gathers_sorted": count_row_sized_gathers(sorted_, n),
        }

    census("groupagg_builtin",
           lambda sf: trace_groupagg(n, ngroups, sf, backend))
    for name, (prog, env) in _programs().items():
        census(f"aggcall_{name}",
               lambda sf, p=prog, e=env: trace_agg_call(p, e, cat, sf,
                                                        ngroups, backend))
    return out


def main() -> int:
    failures = []
    for backend, (n, ng) in (("jnp", (50_000, 512)),
                             ("interpret", (2_000, 64))):
        counts = sortfree_census(n, ng, backend)
        for name, c in counts.items():
            print(f"[{backend} n={n}] {name}: {c}")
            if c["row_sorts_sortfree"] != 0:
                failures.append(f"[{backend}] {name}: sort-free lowering "
                                f"still contains row-sized sorts: {c}")
            if c["row_sorts_sorted"] < 1:
                failures.append(f"[{backend}] {name}: detector sanity — "
                                f"the sorted route should trace to at "
                                f"least one row-sized sort: {c}")
            if c["row_gathers_sorted"] != 0:
                failures.append(f"[{backend}] {name}: the sorted route "
                                f"gathers rows by the sort's permutation "
                                f"instead of carrying them: {c}")
            if c["row_gathers_sortfree"] > c["row_gathers_sorted"]:
                failures.append(f"[{backend}] {name}: sort-free lowering "
                                f"adds row-sized gathers over the sorted "
                                f"route: {c}")
    if failures:
        for f in failures:
            print("FAIL:", f, file=sys.stderr)
        return 1
    print("OK: sort-free grouped lowering contains zero row-capacity-sized "
          "sorts and no new row-sized gathers (sorted route keeps its "
          "sort, so the census would catch a regression)")
    return 0


if __name__ == "__main__":
    sys.exit(main())
