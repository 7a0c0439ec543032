"""Roofline arithmetic and the peak table of the benchmark."""
import _paths  # noqa: F401
import pytest

from chipbench import roofline
from chipbench.data import loops, tpch


def test_peaks_known_kind_and_unknown_kind_raises():
    p = roofline.peaks("TPU v5 lite")
    assert p["hbm_bytes_per_s"] == 819e9
    assert p["bf16_flops_per_s"] == 197e12
    assert "TPU v5e" in p["source"]
    with pytest.raises(KeyError):
        roofline.peaks("TPU v9 imaginary")
    with pytest.raises(KeyError):
        roofline.min_seconds(1.0, "cpu")


@pytest.mark.parametrize("name,want", [
    # 60M rows x (l_orderkey, l_quantity + group id) x 4 B = 720 MB
    ("Q18", 60_000_000 * 3 * 4),
    ("Q21", 60_000_000 * 4 * 4),
    ("Q13", 15_000_000 * 3 * 4),
    # PARTSUPP's three columns + group id, and SUPPLIER's two (the join)
    ("Q2", 8_000_000 * 4 * 4 + 100_000 * 2 * 4),
])
def test_loop_input_bytes_come_from_plan_shapes(name, want):
    n = tpch.sizes(10)
    assert roofline.input_bytes(loops.reads(name), n) == want


@pytest.mark.parametrize("name,groups", [
    ("Q18", 15_000_000), ("Q2", 2_000_000), ("Q21", 100_000),
    # customers whose key is a multiple of 3 have no orders
    ("Q13", 1_000_000),
])
def test_loop_result_bytes(name, groups):
    n = tpch.sizes(10)
    assert loops.groups(name, n) == groups
    assert roofline.output_bytes(loops.groups(name, n), 2) == groups * 8


def test_min_seconds_at_v5e_bandwidth():
    assert roofline.min_seconds(819e9, "TPU v5 lite") == pytest.approx(1.0)
    assert roofline.min_seconds(720e6, "TPU v5 lite") == \
        pytest.approx(720e6 / 819e9)


def test_roofline_reader_counts_a_coalesced_launch_once():
    from types import SimpleNamespace
    from chipbench.harness import load_module
    read = load_module("metrics", "kernel_roofline_pct").read
    tr = SimpleNamespace(category_s=lambda c: 0.01 if c == "kernel" else 0)
    run = SimpleNamespace(
        trace=tr, device_kind="TPU v5 lite",
        launches=[("Q18", [object()] * 4)],
        bytes_per_launch={"Q18": 819e6}, bytes_per_result={"Q18": 0})
    # 819 MB once is 1 ms at peak: a tenth of a 10 ms kernel
    assert read(run) == pytest.approx(10.0)
    assert read(SimpleNamespace(trace=None)) is None
