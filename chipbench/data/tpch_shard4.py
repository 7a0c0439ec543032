"""The TPC-H catalog of ``chipbench/data/tpch.py``, made directly in its
row split over four chips.

The rows are those ``tpch.generate`` makes from the same seed at the same
scale (the same jitted ``tpch._columns``; JAX's partitionable threefry
makes each random value from the key and its index alone), but the one
call is compiled with output shardings: LINEITEM, ORDERS and PARTSUPP are
split in contiguous row ranges over the four chips of one mesh axis, and
PART, CUSTOMER and SUPPLIER are whole on every chip.  Each chip computes
its own shard, so nothing of the catalog is staged on one chip.

Both generators lay the split tables out in key order: LINEITEM and
ORDERS by order key, PARTSUPP by part key.  A split by row ranges is then
a split by key ranges, as an MPP warehouse distributes its fact tables,
and each split table declares it (``Table.shard_rows(ordered_by=...)``).
"""
from __future__ import annotations

import numpy as np

from chipbench.data import tpch

#: chips of the mesh and the name of its one axis
CHIPS = 4
AXIS = "rows"
#: tables split by row ranges, with the key whose ranges they are
SPLIT = {"LINEITEM": ("l_orderkey",), "ORDERS": ("o_orderkey",),
         "PARTSUPP": ("ps_partkey",)}

SCHEMAS = tpch.SCHEMAS
sizes = tpch.sizes
host_rng = tpch.host_rng


def mesh():
    """The mesh over the first ``CHIPS`` devices, or over every device
    where JAX has fewer (the control and tests on one CPU device: a
    split over one device is no split, and the rows are the same)."""
    import jax
    from jax.sharding import Mesh
    return Mesh(np.asarray(jax.devices()[:CHIPS]), (AXIS,))


def generate(scale: float, seed: int) -> dict:
    """The catalog as ``Table``s, each split table row-split over the
    mesh with its key ranges declared, made from ``seed`` in one jitted
    call."""
    import jax
    from jax.sharding import NamedSharding, PartitionSpec
    from repro.relational import Table

    n = sizes(scale)
    m = mesh()
    odd = [t for t in SPLIT if n[t] % m.size]
    if odd:
        raise ValueError(f"rows of {odd} at scale {scale} do not divide "
                         f"over {m.size} devices")
    rows = NamedSharding(m, PartitionSpec(AXIS))
    whole = NamedSharding(m, PartitionSpec())
    shardings = {t: {c: rows if t in SPLIT else whole for c in cols}
                 for t, cols in SCHEMAS.items()}
    key = jax.random.key(tpch.seed32(seed))
    made = jax.jit(lambda k: tpch._columns(k, n),
                   out_shardings=shardings)(key)
    return {t: (Table(c).shard_rows(m, AXIS, ordered_by=SPLIT[t])
                if t in SPLIT else Table(c))
            for t, c in made.items()}
