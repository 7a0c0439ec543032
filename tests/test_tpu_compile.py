"""Compile the main-path kernels at TPC-H SF 10 sizes for a described
TPU v5e — no chip needed, the TPU compiler is installed.  Interpret-mode
tests cannot see what only the chip's compiler refuses: scalar-prefetch
maps that overflow SMEM, operands padded past HBM, a Mosaic kernel GSPMD
cannot partition.  Each test asserts the compiled program holds the
kernel (``tpu_custom_call``), under the name its ``pallas_call`` gives
it, and the sorted route keeps its named scopes in the optimized HLO's
metadata, where the profiler reads them.

The topology is described inside a module fixture, never at import: only
one process may load the TPU library at a time, so every pytest worker
must collect these tests and only the one running them loads it.
"""
from __future__ import annotations

import os
import re

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import Mesh, NamedSharding, SingleDeviceSharding
from jax.sharding import PartitionSpec as P

from repro.kernels.segment_agg import (fused_segment_agg,
                                       launched_grid_steps,
                                       normalize_moments)
from repro.launch.sharded_agg import sharded_fused_segment_agg
from repro.relational import Table, execute, keyslot
from repro.relational.engine import segment_ids_for
from repro.relational.group_bound import resolve_group_bound
from repro.relational.plan import GroupAgg, Scan

LINEITEM_SF10 = 60_000_000
PARTSUPP_SF10 = 8_000_000


@pytest.fixture(scope="module")
def topo():
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from jax.experimental import topologies
    try:
        return topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:  # noqa: BLE001 — any failure means "cannot"
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")


@pytest.fixture(scope="module")
def one_chip(topo):
    return SingleDeviceSharding(topo.devices[0])


def _compiled_text(fn, *args) -> str:
    return jax.jit(fn).lower(*args).compile().as_text()


def _args(n, cols, sharding):
    return (jax.ShapeDtypeStruct((n, cols), jnp.float32, sharding=sharding),
            jax.ShapeDtypeStruct((n,), jnp.int32, sharding=sharding),
            jax.ShapeDtypeStruct((n, cols), jnp.bool_, sharding=sharding))


def test_sorted_pruned_kernel_q21_shape_splits(one_chip):
    # Q21: 60M line items grouped by 100k suppliers — the pruned grid's
    # step maps would need 1.8 MiB of SMEM in one launch
    nseg = (1 << 17) + 1
    assert launched_grid_steps(LINEITEM_SF10, nseg) > 1 << 17
    txt = _compiled_text(
        lambda v, s, g: fused_segment_agg(v, s, g, nseg, backend="pallas",
                                          moments=("sum", "count"),
                                          assume_sorted=True),
        *_args(LINEITEM_SF10, 1, one_chip))
    assert txt.count("tpu_custom_call") >= 4      # one launch per row range
    assert "segment_agg_sorted" in txt


def test_unsorted_single_tile_kernel_60m(one_chip):
    txt = _compiled_text(
        lambda v, s, g: fused_segment_agg(v, s, g, 513, backend="pallas",
                                          layout="unsorted"),
        *_args(LINEITEM_SF10, 3, one_chip))
    assert "tpu_custom_call" in txt
    assert "segment_agg_unsorted" in txt


def test_sorted_route_names_its_sort_and_gathers_no_rows(one_chip):
    # the group sort keeps its scope through optimization, and the
    # columns ride through it: no gather of the table's row count is
    # left to apply its permutation (a 64k-row table: the stable sort
    # costs the TPU compiler most of a minute at any size)
    n = 1 << 16
    t = Table({"k": jax.ShapeDtypeStruct((n,), jnp.int32, sharding=one_chip),
               "q": jax.ShapeDtypeStruct((n,), jnp.float32,
                                         sharding=one_chip)},
              jax.ShapeDtypeStruct((n,), jnp.bool_, sharding=one_chip))
    txt = _compiled_text(
        lambda t: segment_ids_for(t, ("k",), (1 << 12) + 1), t)
    names = set(re.findall(r'op_name="([^"]*)"', txt))
    assert any(re.search(r"\bgroup_sort/sort$", n) for n in names)
    assert not re.search(rf"\[{n}\]\S* gather\(", txt)


def test_index_moment_kernel_q2_partsupp(one_chip):
    moms = normalize_moments((("min", "argmin_first"),), 1)
    txt = _compiled_text(
        lambda v, s, g: fused_segment_agg(v, s, g, (1 << 21) + 1,
                                          backend="pallas", moments=moms,
                                          assume_sorted=True),
        *_args(PARTSUPP_SF10, 1, one_chip))
    assert "tpu_custom_call" in txt


@pytest.fixture(scope="module")
def mesh4(topo):
    return Mesh(np.asarray(topo.devices[:4]), ("data",))


@pytest.mark.parametrize("layout,nseg", [("sorted", (1 << 17) + 1),
                                         ("unsorted", 4097)])
def test_sharded_fused_segment_agg_four_chips(mesh4, layout, nseg):
    # sorted: Q21's pruned launch per shard; unsorted: the global slot
    # ids the serving layer caches for a row-split table (tile_ship's
    # ship-date domain), each shard's kernel under shard_map
    rows = NamedSharding(mesh4, P("data"))
    txt = _compiled_text(
        lambda v, s, g: sharded_fused_segment_agg(
            v, s, g, nseg, mesh=mesh4, axis="data", backend="pallas",
            moments=("sum", "count", "max"), assume_sorted=True,
            layout=layout),
        *_args(LINEITEM_SF10, 1, rows))
    assert "tpu_custom_call" in txt
    assert "all-reduce" in txt


def test_served_groupagg_over_row_split_table_four_chips(mesh4,
                                                         monkeypatch):
    """What ``AggServer`` traces for a ``shard_rows`` table: the table's
    declared split rides through ``jit`` and the cached global slots feed
    each shard's unsorted kernel — GSPMD alone cannot partition it."""
    monkeypatch.setenv("REPRO_GROUPAGG_FUSED", "pallas")
    rows = NamedSharding(mesh4, P("data"))
    n = LINEITEM_SF10
    _, bound = resolve_group_bound(2556, n)
    t = Table({"d": jax.ShapeDtypeStruct((n,), jnp.int32, sharding=rows),
               "q": jax.ShapeDtypeStruct((n,), jnp.float32, sharding=rows)},
              jax.ShapeDtypeStruct((n,), jnp.bool_, sharding=rows),
              row_split=(mesh4, "data"))
    slots = (jax.ShapeDtypeStruct((n,), jnp.int32, sharding=rows),
             jax.ShapeDtypeStruct((bound,), jnp.int32),
             jax.ShapeDtypeStruct((bound,), jnp.bool_),
             jax.ShapeDtypeStruct((), jnp.int32))
    plan = GroupAgg(Scan("L", ("d", "q")), ("d",),
                    (("qty", "sum", "q"), ("hi", "max", "q")),
                    max_groups=2556)

    def run(tables, slots):
        with keyslot.provide_slots({(("d",), bound): slots}):
            return execute(plan, tables).columns

    txt = _compiled_text(run, {"L": t}, slots)
    assert "tpu_custom_call" in txt
    assert "all-reduce" in txt


def test_sorted_route_over_row_split_table_four_chips(mesh4, monkeypatch):
    """The sorted route over a row-split LINEITEM-sized table, shard by
    shard: each chip sorts its own quarter of the rows (scope
    ``group_sort``), no op sorts, gathers or all-gathers the table's row
    count, every collective moves at most the segment range (scope
    ``shard_merge``), and the program fits a v5e's 16 GiB on each chip.
    One program holds both merges: by declared key ranges (a Q18-like
    grouping, up to a quarter of the rows in groups) and by all-gathered
    key tables (a Q21-like one under a 100k-group bound)."""
    monkeypatch.setenv("REPRO_GROUPAGG_FUSED", "pallas")
    monkeypatch.setenv("REPRO_GROUPAGG_SORTFREE", "off")
    rows = NamedSharding(mesh4, P("data"))
    n = LINEITEM_SF10
    t = Table({c: jax.ShapeDtypeStruct((n,), d, sharding=rows)
               for c, d in (("k", jnp.int32), ("s", jnp.int32),
                            ("q", jnp.float32))},
              jax.ShapeDtypeStruct((n,), jnp.bool_, sharding=rows),
              row_split=(mesh4, "data", ("k",)))
    by_k = GroupAgg(Scan("L", ("k", "s", "q")), ("k",),
                    (("qty", "sum", "q"),), max_groups=n // 4)
    by_s = GroupAgg(Scan("L", ("k", "s", "q")), ("s",),
                    (("qty", "sum", "q"),), max_groups=100_000)
    nseg = max(resolve_group_bound(p.max_groups, n)[0] for p in (by_k,
                                                                by_s))
    compiled = jax.jit(lambda c: (execute(by_k, c).columns,
                                  execute(by_s, c).columns)).lower(
        {"L": t}).compile()
    txt = compiled.as_text()
    names = set(re.findall(r'op_name="([^"]*)"', txt))
    assert any(re.search(r"\bgroup_sort/sort$", m) for m in names)
    assert any("shard_merge" in m for m in names)
    # after partitioning no op holds the global row count; every sort
    # (the group sorts, and the scatters XLA lowers to sorts) holds at
    # most a shard's rows
    assert f"[{n}]" not in txt
    sort_dims = {int(d) for line in txt.splitlines() if " sort(" in line
                 for d in re.findall(r"\[(\d+)\]", line.split(" sort(")[0])}
    assert n // 4 in sort_dims and max(sort_dims) <= n // 4
    for line in re.findall(r"= .*? (?:all-reduce|all-gather|all-to-all|"
                           r"collective-permute|reduce-scatter)"
                           r"(?:-start)?\(", txt):
        dims = [int(d) for d in re.findall(r"\[([\d,]+)\]", line)
                for d in d.split(",")]
        assert max(dims, default=0) <= nseg, line
    mem = compiled.memory_analysis()
    assert (mem.temp_size_in_bytes + mem.argument_size_in_bytes
            + mem.output_size_in_bytes) < 16 << 30
