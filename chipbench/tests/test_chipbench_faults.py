"""Drives a whole run of each cell on the CPU at a tiny scale — everything
but the look for a chip — with the timed path broken underneath, and sees
``correct`` come out false, once for each fault the cell can have:

* ``answer``: one served answer altered where the server produces it;
* ``half``: every other row of every table left out of each launch (so
  a mean is taken over the rest);
* ``fallback``: the primary executable raises on every launch, so the
  server serves each batch from its jnp fallback — exact answers from
  another path than the one measured.

The loop cell is read-only (no state for a step to leave unchanged) and
runs on one chip (no exchange between chips)."""
import _paths

import pytest

from chipbench import harness

BENCH = _paths.bench()
TINY = {"scale": 0.001}


def _altered_answers(monkeypatch):
    import jax.numpy as jnp
    from repro.relational import Table
    from repro.serve import agg_server

    real = agg_server.AggServer._result

    def altered(self, request, table):
        col = sorted(table.columns)[-1]
        a = table.columns[col]
        first = jnp.argmax(table.mask())
        cols = dict(table.columns)
        cols[col] = a.at[first].set(a[first] + jnp.ones((), a.dtype))
        return real(self, request, Table(cols, table.valid))

    monkeypatch.setattr(agg_server.AggServer, "_result", altered)


def _half_rows(monkeypatch):
    import jax.numpy as jnp
    from repro.relational import Table
    from repro.serve import agg_server

    real = agg_server.execute

    def half(plan, tables, env=None):
        cut = {}
        for name, t in tables.items():
            keep = jnp.arange(t.capacity) % 2 == 0
            cut[name] = Table(t.columns, t.mask() & keep, t.group_bound,
                              row_split=t.row_split)
        return real(plan, cut, env)

    monkeypatch.setattr(agg_server, "execute", half)


def _primary_fails(monkeypatch):
    from repro.serve import agg_server

    real = agg_server.AggServer._launch_bucket

    def failing(self, ent, psig, plist, degraded=False):
        if not degraded:
            raise RuntimeError("planted: primary executable failed")
        return real(self, ent, psig, plist, degraded=True)

    monkeypatch.setattr(agg_server.AggServer, "_launch_bucket", failing)


FAULTS = {"answer": _altered_answers, "half": _half_rows,
          "fallback": _primary_fails}
CASES = [("loops.closed4", "answer"), ("loops.closed4", "half"),
         ("loops.closed4", "fallback")]


@pytest.mark.parametrize("cell,fault", CASES)
def test_broken_path_is_not_correct(cell, fault, monkeypatch):
    FAULTS[fault](monkeypatch)
    out = harness.run(BENCH, cell, 2 ** 34 + 3, 1.0, False,
                      config_override=TINY, log=lambda *a: None)
    assert out["correct"] is False, out["checks"]
    assert list(out)[-1] == "checks"
    if fault == "fallback":
        answers = {k: c for k, c in out["checks"].items()
                   if not k.startswith("guard.")}
        assert all(c["value"] <= c["limit"] for c in answers.values())
        assert out["checks"]["guard.backend_failures"]["value"] > 0


def test_sound_run_is_correct():
    out = harness.run(BENCH, "loops.closed4", 2 ** 34 + 3, 1.0, False,
                      config_override=TINY, log=lambda *a: None)
    assert out["correct"] is True, out["checks"]
    assert out["failed"] == 0 and out["attempted"] > 0
    assert {k for k in out["checks"] if k.startswith("guard.")} == {
        f"guard.{k}" for k in harness.GUARD_COUNTERS}
