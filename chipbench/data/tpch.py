"""TPC-H catalog generator of the benchmark, on the device.

Populates the six tables TPC-H spec 3.0.1 Clause 4.2.3 defines, at the
cardinalities of Clause 4.2.5, with the spec's keys and value rules:

* keys from 1: ``p_partkey``, ``s_suppkey``, ``c_custkey`` dense;
  ``o_orderkey`` sparse as dbgen makes it (of every 32 keys the first 8
  are used, so SF 10's 15M orders span keys up to 60M);
* ``o_custkey`` uniform over the customers whose key is not a multiple
  of 3, so a third of the customers have no orders;
* the four suppliers of a part, ``ps_suppkey`` = (partkey + i (S/4 +
  (partkey - 1) / S)) mod S + 1 for i in 0..3, and ``l_suppkey`` one of
  them at random;
* ``p_retailprice`` by the spec's formula, ``l_extendedprice`` =
  quantity x the part's retail price, ``o_totalprice`` the sum over the
  order's line items, ``o_orderstatus`` from their line status;
* dates as integer days from 1992-01-01: order dates up to 151 days
  before 1998-12-31, ship = order + 1..121, commit = order + 30..90,
  receipt = ship + 1..30; return flag and line status from the spec's
  current date 1995-06-17;
* strings as dictionary codes (int32), decimals as float32 of whole
  cents; ``o_comment_special`` and ``p_type_promo`` stand for the
  comment and type predicates the loops test.

Line items per order: dbgen draws 1..7; here every table of orders holds
each count 1..7 equally often, in a seeded order, so LINEITEM holds
exactly 4 rows per order whatever the seed.

The whole catalog is made in one jitted call from the seed, so set-up
pays no host generation and no host-to-device copy.  The system has a
seeded generator of its own (``repro.relational.tpch``); this one is the
benchmark's, so a change there cannot move the benchmark's data.
"""
from __future__ import annotations

import numpy as np

#: 1995-06-17, the spec's CURRENTDATE, in days from 1992-01-01
CURRENT_DAY = 1263
#: 1998-12-31, the spec's ENDDATE, in days from 1992-01-01
END_DAY = 2556
#: line items per order: each count 1..7 equally often, mean 4
LINES_PER_ORDER = 4

SCHEMAS = {
    "PART": ("p_partkey", "p_name", "p_mfgr", "p_brand", "p_type",
             "p_type_promo", "p_size", "p_container", "p_retailprice",
             "p_comment"),
    "SUPPLIER": ("s_suppkey", "s_name", "s_address", "s_nationkey",
                 "s_phone", "s_acctbal", "s_comment"),
    "PARTSUPP": ("ps_partkey", "ps_suppkey", "ps_availqty",
                 "ps_supplycost", "ps_comment"),
    "CUSTOMER": ("c_custkey", "c_name", "c_address", "c_nationkey",
                 "c_phone", "c_acctbal", "c_mktsegment", "c_comment"),
    "ORDERS": ("o_orderkey", "o_custkey", "o_orderstatus", "o_totalprice",
               "o_orderdate", "o_orderpriority", "o_clerk",
               "o_shippriority", "o_comment", "o_comment_special"),
    "LINEITEM": ("l_orderkey", "l_partkey", "l_suppkey", "l_linenumber",
                 "l_quantity", "l_extendedprice", "l_discount", "l_tax",
                 "l_returnflag", "l_linestatus", "l_shipdate",
                 "l_commitdate", "l_receiptdate", "l_shipinstruct",
                 "l_shipmode", "l_comment"),
}


def sizes(scale: float) -> dict:
    """Row counts at a scale factor (TPC-H spec Clause 4.2.5), with floors
    for tiny CPU scales."""
    n_part = max(8, int(200_000 * scale))
    n_ord = max(16, int(1_500_000 * scale))
    return {"PART": n_part, "SUPPLIER": max(4, int(10_000 * scale)),
            "PARTSUPP": n_part * 4, "CUSTOMER": max(8, int(150_000 * scale)),
            "ORDERS": n_ord, "LINEITEM": n_ord * LINES_PER_ORDER}


def line_counts(m: int) -> np.ndarray:
    """Line items of ``m`` orders, before shuffling: 1..7 equally often,
    the remainder in pairs summing to 8 (and one 4), so they total 4m."""
    r = m % 7
    rest = [4] * (r % 2) + [1, 7, 2, 6, 3, 5][:r - r % 2]
    return np.concatenate([np.tile(np.arange(1, 8), m // 7),
                           rest]).astype(np.int32)


def orderkeys(i):
    """dbgen's sparse order key of the ``i``-th order (from 1): the low 3
    bits kept, the rest shifted up by 2."""
    return ((i >> 3) << 5) | (i & 7)


def custkeys(r):
    """The ``r``-th (from 0) customer key that is not a multiple of 3."""
    return r + r // 2 + 1


def suppkey(partkey, i, n_supp: int):
    """The ``i``-th (0..3) supplier of a part (Clause 4.2.3, PS_SUPPKEY)."""
    return (partkey + i * (n_supp // 4 + (partkey - 1) // n_supp)) \
        % n_supp + 1


def retailprice(partkey):
    """P_RETAILPRICE in cents (Clause 4.2.3)."""
    return 90000 + (partkey // 10) % 20001 + 100 * (partkey % 1000)


def seed32(seed: int) -> int:
    """A 31-bit device seed that depends on every bit of ``seed`` (JAX
    keeps only the low 32 bits of a seed)."""
    ss = np.random.SeedSequence(int(seed) % (1 << 64))
    return int(ss.generate_state(1)[0]) & 0x7FFFFFFF


def host_rng(seed: int, stream: int) -> np.random.Generator:
    """Host generator for one named use of the seed (traffic)."""
    return np.random.default_rng([int(seed) % (1 << 64), stream])


def _columns(key, n: dict):
    """Column arrays at the exact row counts ``n`` (traced)."""
    import jax
    import jax.numpy as jnp

    i32, f32 = jnp.int32, jnp.float32
    keys = iter(jax.random.split(key, 64))

    def ints(m, lo, hi):
        """Uniform integers in [lo, hi]."""
        return jax.random.randint(next(keys), (m,), lo, hi + 1, i32)

    def cents(m, lo, hi):
        """Uniform decimals in [lo, hi] cents, as float32 units."""
        return ints(m, lo, hi).astype(f32) / 100

    def flag(m, p):
        return jax.random.bernoulli(next(keys), p, (m,))

    np_, ns, nps = n["PART"], n["SUPPLIER"], n["PARTSUPP"]
    nc, no, nl = n["CUSTOMER"], n["ORDERS"], n["LINEITEM"]
    big = (1 << 30) - 1

    partkey = jnp.arange(1, np_ + 1, dtype=i32)
    mfgr = ints(np_, 0, 4)
    part = dict(
        p_partkey=partkey, p_name=ints(np_, 0, big), p_mfgr=mfgr,
        p_brand=mfgr * 5 + ints(np_, 0, 4), p_type=ints(np_, 0, 149),
        p_type_promo=flag(np_, 0.2), p_size=ints(np_, 1, 50),
        p_container=ints(np_, 0, 39),
        p_retailprice=retailprice(partkey).astype(f32) / 100,
        p_comment=ints(np_, 0, big))
    suppkeys = jnp.arange(1, ns + 1, dtype=i32)
    supplier = dict(
        s_suppkey=suppkeys, s_name=suppkeys, s_address=ints(ns, 0, big),
        s_nationkey=ints(ns, 0, 24), s_phone=ints(ns, 0, big),
        s_acctbal=cents(ns, -99_999, 999_999), s_comment=ints(ns, 0, big))
    ps_partkey = jnp.repeat(partkey, 4)
    partsupp = dict(
        ps_partkey=ps_partkey,
        ps_suppkey=suppkey(ps_partkey, jnp.arange(nps, dtype=i32) % 4, ns),
        ps_availqty=ints(nps, 1, 9_999),
        ps_supplycost=cents(nps, 100, 100_000),
        ps_comment=ints(nps, 0, big))
    custkey = jnp.arange(1, nc + 1, dtype=i32)
    customer = dict(
        c_custkey=custkey, c_name=custkey, c_address=ints(nc, 0, big),
        c_nationkey=ints(nc, 0, 24), c_phone=ints(nc, 0, big),
        c_acctbal=cents(nc, -99_999, 999_999), c_mktsegment=ints(nc, 0, 4),
        c_comment=ints(nc, 0, big))

    # line items: each order's rows are contiguous, in order-key order
    counts = (jnp.arange(no, dtype=i32) % 7) + 1
    rem = no % 7
    if rem:
        tail = jnp.asarray(line_counts(no)[no - rem:])
        counts = counts.at[no - rem:].set(tail)
    counts = jax.random.permutation(next(keys), counts)
    first = jnp.cumsum(counts) - counts
    order = jnp.repeat(jnp.arange(no, dtype=i32), counts,
                       total_repeat_length=nl)
    o_orderkey = orderkeys(jnp.arange(1, no + 1, dtype=i32))
    orderdate = ints(no, 0, END_DAY - 151)
    odate = orderdate[order]
    ship = odate + ints(nl, 1, 121)
    receipt = ship + ints(nl, 1, 30)
    l_partkey = ints(nl, 1, np_)
    quantity = ints(nl, 1, 50)
    price = (quantity * retailprice(l_partkey)).astype(f32) / 100
    discount = ints(nl, 0, 10).astype(f32) / 100
    tax = ints(nl, 0, 8).astype(f32) / 100
    linestatus = (ship > CURRENT_DAY).astype(i32)           # 1 = 'O'
    lineitem = dict(
        l_orderkey=o_orderkey[order], l_partkey=l_partkey,
        l_suppkey=suppkey(l_partkey, ints(nl, 0, 3), ns),
        l_linenumber=(jnp.arange(nl, dtype=i32) - first[order] + 1),
        l_quantity=quantity.astype(f32), l_extendedprice=price,
        l_discount=discount, l_tax=tax,
        # 0 = 'N'; 1, 2 = 'R', 'A' once received by the current date
        l_returnflag=jnp.where(receipt <= CURRENT_DAY, ints(nl, 1, 2), 0),
        l_linestatus=linestatus, l_shipdate=ship,
        l_commitdate=odate + ints(nl, 30, 90), l_receiptdate=receipt,
        l_shipinstruct=ints(nl, 0, 3), l_shipmode=ints(nl, 0, 6),
        l_comment=ints(nl, 0, big))

    def per_order(op, v):
        return op(v, order, num_segments=no, indices_are_sorted=True)
    open_lines = per_order(jax.ops.segment_sum, linestatus)
    orders = dict(
        o_orderkey=o_orderkey,
        o_custkey=custkeys(ints(no, 0, nc - nc // 3 - 1)),
        # 0 = 'F' (every line shipped), 1 = 'O' (none), 2 = 'P'
        o_orderstatus=jnp.where(open_lines == 0, 0,
                                jnp.where(open_lines == counts, 1, 2)),
        o_totalprice=per_order(jax.ops.segment_sum,
                               price * (1 + tax) * (1 - discount)),
        o_orderdate=orderdate, o_orderpriority=ints(no, 0, 4),
        o_clerk=ints(no, 0, max(1, no // 1500) - 1),
        o_shippriority=jnp.zeros(no, i32), o_comment=ints(no, 0, big),
        o_comment_special=flag(no, 0.01))
    return {"PART": part, "SUPPLIER": supplier, "PARTSUPP": partsupp,
            "CUSTOMER": customer, "ORDERS": orders, "LINEITEM": lineitem}


def generate(scale: float, seed: int) -> dict:
    """The catalog as ``Table``s on the default device, made from
    ``seed`` in one jitted call."""
    import jax
    from repro.relational import Table

    n = sizes(scale)
    key = jax.random.key(seed32(seed))
    made = jax.jit(lambda k: _columns(k, n))(key)
    return {t: Table(c) for t, c in made.items()}
