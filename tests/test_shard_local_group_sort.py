"""The sorted route over a row-split table, shard by shard
(``launch/sharded_agg.py`` ``shard_local_segments``): each of four
devices sorts its own rows, and the partial groups merge across devices
by key.  The cases run in one subprocess with four host devices
(``tests/_shard_local_cases.py``); each compares, bit for bit on sums and
counts, the row-split run (eager and under ``jit``, a grouped cursor
loop and a ``GroupAgg``) with the one-device run and a numpy reference,
but ``overflow``, whose groups exceed the declared bound: there an eager
run must raise and a traced one come back poisoned whole.

The sort-free route is switched off in the subprocess: on the CPU its
kernel-grid gate passes at every size, where on the chip the plans of
the four-chip cell take the sorted route
(``chipbench/tests/test_chipbench_shard4.py``)."""
import json
import os
import subprocess
import sys

import pytest

HERE = os.path.dirname(os.path.abspath(__file__))
SRC = os.path.join(HERE, "..", "src")

#: case → the merges its tables take, in the order they run
CASES = {
    "one_shard": ["ranges"] * 2,
    "every_shard": ["keys"] * 2,
    "straddles": ["ranges"] * 2 + ["keys"] * 2,
    "absent": ["keys"] * 2 + ["ranges"] * 2,
    "empty_shard": ["ranges"] * 2 + ["keys"] * 2,
    "invalid": ["ranges"] * 2 + ["keys"] * 2,
    "nan_key": ["keys"] * 2 + ["ranges"] * 2,
    "overflow": ["ranges"] * 2 + ["keys"] * 2,
}


def _four_devices(code_or_path, extra_env=()):
    env = {**os.environ, "JAX_PLATFORMS": "cpu",
           "XLA_FLAGS": (os.environ.get("XLA_FLAGS", "")
                         + " --xla_force_host_platform_device_count=4"),
           "PYTHONPATH": os.path.abspath(SRC) + os.pathsep
           + os.environ.get("PYTHONPATH", ""), **dict(extra_env)}
    return subprocess.run([sys.executable, code_or_path],
                          capture_output=True, text=True, env=env,
                          timeout=600)


@pytest.fixture(scope="module")
def results():
    r = _four_devices(os.path.join(HERE, "_shard_local_cases.py"),
                      {"REPRO_GROUPAGG_SORTFREE": "off"})
    assert r.returncode == 0, r.stderr[-4000:]
    return json.loads(r.stdout.strip().splitlines()[-1])


@pytest.mark.parametrize("case", list(CASES))
def test_shard_local_route_matches_one_device_and_reference(results, case):
    got = results[case]
    assert got["merges"] == CASES[case]
    assert got["errors"] == []
