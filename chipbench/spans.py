"""The program's own spans and named scopes in a profiler trace, and the
numbers read from them.

``trace_reduce.reduce`` keeps a trace's device ops and the benchmark's
window.  This module reads the rest of the same ``.xplane.pb``:

* the program's host spans (``Span``): the ``jax.profiler.TraceAnnotation``
  events whose names start with ``agg.`` (``AggServer``'s dispatcher) or
  ``table.`` (``Table.to_numpy``), their TraceMe metadata as fields;
* the innermost ``jax.named_scope`` of each device op, from the HLO
  ``op_name`` the profiler records with it (``chipbench/xplane_scopes.py``).

``reduce(path)`` returns both with the ``Trace`` as a ``Traced``; the
functions below it read one number each.  The harness deletes its trace
directory after ``trace_reduce.reduce``, so these are not benchmark
metrics: ``chipbench/tools/span_report.py`` prints them for a trace file.
"""
from __future__ import annotations

from dataclasses import dataclass, field
from types import SimpleNamespace
from typing import Optional

from chipbench import trace_reduce
from chipbench.trace_reduce import Trace, merged, own_time

#: name prefixes of the program's host spans
SPAN_PREFIXES = ("agg.", "table.")
#: dispatcher spans in which the host waits rather than works: on the
#: device (``agg.await``) or on the batching window's sleep
WAITING = ("agg.await", "agg.coalesce")
GUARD_SCAN = "agg.guard_scan"
SORT_GATHER = "sort_gather"


@dataclass
class Span:
    """A host span of the program (``jax.profiler.TraceAnnotation``)."""
    name: str
    start: int      # ns
    end: int        # ns
    thread: int     # the host line (one per thread) it was recorded on
    fields: dict = field(default_factory=dict)  # TraceMe metadata


@dataclass
class Traced:
    """A reduced trace with the program's spans and op scopes."""
    trace: Trace
    spans: list = field(default_factory=list)
    scopes: list = field(default_factory=list)  # of trace.ops[i], or None


def _span(ev, thread: int) -> Span:
    """A host event as a ``Span``; the profiler has already moved its
    TraceMe metadata (``name#k=v,...#``) into the event's stats."""
    s = int(ev.start_ns)
    return Span(ev.name, s, s + int(ev.duration_ns), thread,
                {k: str(v) for k, v in ev.stats})


def reduce(path: str) -> Traced:
    """``trace_reduce.reduce(path)`` with the spans and the scope of each
    op, walked in the order that function walks the trace."""
    from jax.profiler import ProfileData

    from chipbench.xplane_scopes import line_scopes
    tr = trace_reduce.reduce(path)
    named = line_scopes(path, trace_reduce.OPS_LINE)
    spans, scopes, thread = [], [], 0
    for plane in ProfileData.from_file(path).planes:
        if plane.name.startswith(trace_reduce.DEVICE_PREFIX):
            try:
                int(plane.name.rsplit(":", 1)[1])
            except ValueError:
                continue
            got = named.get(plane.name, [])
            for line in plane.lines:
                if line.name != trace_reduce.OPS_LINE:
                    continue
                for i, ev in enumerate(line.events):
                    scopes.append(got[i][1] if i < len(got)
                                  and got[i][0] == ev.name else None)
            continue
        for line in plane.lines:
            thread += 1
            spans.extend(_span(ev, thread) for ev in line.events
                         if ev.name.startswith(SPAN_PREFIXES))
    assert len(scopes) == len(tr.ops), (len(scopes), len(tr.ops))
    return Traced(tr, spans, scopes)


def guard_scan_ms(t: Traced) -> Optional[float]:
    """Mean milliseconds of the server's poison scan per launch: the
    ``agg.guard_scan`` spans that start inside the window (the result's
    device-to-host copy and the numpy scan, before the next launch)."""
    lo, hi = t.trace.window
    took = [s.end - s.start for s in t.spans
            if s.name == GUARD_SCAN and lo <= s.start < hi]
    return sum(took) * 1e-6 / len(took) if took else None


def idle_pct_dispatch(t: Traced) -> Optional[float]:
    """Share of the window, in percent, in which no op ran on the device
    while the dispatcher did host work: an instant counts when the
    innermost open ``agg.*`` span on its thread is not one of
    ``WAITING``."""
    tr = t.trace
    if not tr.devices or tr.window_s <= 0:
        return None
    spans = [SimpleNamespace(device=s.thread, start=s.start, end=s.end,
                             name=s.name)
             for s in t.spans if s.name.startswith("agg.")]
    if not spans:
        return None
    lo, hi = tr.window
    work = merged([(max(s, lo), min(e, hi)) for s, e, sp in own_time(spans)
                   if sp.name not in WAITING])
    idle = sum((e - s) * 1e-9 - tr.busy_within(s, e) for s, e in work)
    return 100.0 * idle / tr.window_s


def gather_ms(t: Traced, requests: int) -> Optional[float]:
    """Device milliseconds of the ops scoped ``sort_gather`` (the row
    gathers that apply the group sort's permutation, ``Table.take`` after
    ``lax.sort`` in ``Table.sort_by``), each counted for its own time,
    inside the window, per request completed in it."""
    if requests <= 0:
        return None
    mine = {id(o) for o, s in zip(t.trace.ops, t.scopes) if s == SORT_GATHER}
    lo, hi = t.trace.window
    ns = sum(max(0, min(e, hi) - max(s, lo))
             for s, e, o in own_time(t.trace.ops) if id(o) in mine)
    return ns * 1e-6 / requests if ns > 0 else None


def open_span(t: Traced, start: int, end: int) -> str:
    """The innermost ``agg.*`` span open at ``start`` (with its plan), or
    "no span open": a label for ``Trace.idle_gaps``."""
    best = None
    for s in t.spans:
        if s.name.startswith("agg.") and s.start <= start < s.end and (
                best is None or s.start >= best.start):
            best = s
    if best is None:
        return "no span open"
    plan = best.fields.get("plan")
    return f"{best.name} plan={plan}" if plan else best.name
