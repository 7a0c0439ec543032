"""Reduction of a JAX profiler trace to the benchmark's device numbers.

The trace is the ``.xplane.pb`` that ``jax.profiler`` writes, read with
``jax.profiler.ProfileData`` alone.  Device planes are named
``/device:TPU:<n>``; each holds an ``XLA Ops`` line whose events are the
operations that ran on that chip, on the same clock as the host planes.
The benchmark marks the measured window with a host span,
``chipbench.window`` (``jax.profiler.TraceAnnotation``).

On a TPU v5e the ``XLA Ops`` events carry no HLO category stat; each is
named by the op's HLO text (``%sort.32 = (s8[...], ...) sort(...)``), so an
operation is classed by its HLO opcode, the word before the operand list:

* ``sort``: a ``sort`` (the stable group sort of the sorted route);
* ``kernel``: a ``custom-call`` to ``tpu_custom_call``, which is what a
  Pallas kernel lowers to (other custom calls, such as
  ``AllocateBuffer``, are not kernels);
* ``other``: everything else; on the sorted route most of it is the row
  gathers that apply the group sort's permutation (``Table.take``), which
  show as ``kind=kCustom`` fusions and carry no opcode of their own.
"""
from __future__ import annotations

import bisect
import glob
import os
import re
from dataclasses import dataclass, field

WINDOW_SPAN = "chipbench.window"
DEVICE_PREFIX = "/device:"
OPS_LINE = "XLA Ops"


@dataclass
class Op:
    name: str
    start: int      # ns
    end: int        # ns
    category: str   # sort | kernel | other
    device: int


@dataclass
class Trace:
    """Device ops of one traced window (ns)."""
    window: tuple
    ops: list = field(default_factory=list)
    devices: int = 0

    @property
    def window_s(self) -> float:
        return (self.window[1] - self.window[0]) * 1e-9

    def _merged(self) -> dict:
        """device → merged op intervals, computed once."""
        if "_m" not in self.__dict__:
            self.__dict__["_m"] = {
                d: merged([(o.start, o.end) for o in self.ops
                           if o.device == d])
                for d in {o.device for o in self.ops}}
        return self.__dict__["_m"]

    def busy_within(self, start: int, end: int) -> float:
        """Device-busy seconds inside [start, end], averaged over the
        devices that ran any op."""
        if not self.devices:
            return 0.0
        total = 0
        for ivs in self._merged().values():
            i = bisect.bisect_left(ivs, [start, start]) - 1
            for s, e in ivs[max(i, 0):]:
                if s >= end:
                    break
                total += max(0, min(e, end) - max(s, start))
        return total * 1e-9 / self.devices

    def busy_s(self) -> float:
        """Union of op intervals inside the window, averaged over the
        devices that ran any op."""
        return self.busy_within(*self.window)

    def _own(self) -> list:
        """[(start, end, op)]: each op's own time, computed once."""
        if "_o" not in self.__dict__:
            self.__dict__["_o"] = own_time(self.ops)
        return self.__dict__["_o"]

    def category_s(self, category: str) -> float:
        """Device time of one class of ops inside the window, each op
        counted for its own time (``own_time``)."""
        lo, hi = self.window
        return sum(max(0, min(e, hi) - max(s, lo))
                   for s, e, o in self._own() if o.category == category) * 1e-9

    def top_ops(self, n: int = 10, width: int = 160) -> list:
        """[[name, seconds]] of the ops that took most device time of
        their own (each op's HLO text, cut to ``width`` characters)."""
        lo, hi = self.window
        acc: dict = {}
        for s, e, o in self._own():
            acc[o.name] = acc.get(o.name, 0) + max(
                0, min(e, hi) - max(s, lo))
        top = sorted(acc.items(), key=lambda kv: -kv[1])[:n]
        return [[k[:width], v * 1e-9] for k, v in top if v > 0]

    def idle_gaps(self, label, n: int = 10) -> list:
        """[[what the host was doing, seconds]] of the longest gaps in
        which no op ran on device 0; ``label(start, end)`` names a gap."""
        lo, hi = self.window
        m = self._merged()
        ivs = m[min(m)] if m else []
        gaps, t = [], lo
        for s, e in ivs:
            s, e = max(s, lo), min(e, hi)
            if e <= lo or s >= hi:
                continue
            if s > t:
                gaps.append((t, s))
            t = max(t, e)
        if t < hi:
            gaps.append((t, hi))
        gaps.sort(key=lambda g: g[0] - g[1])
        return [[label(s, e), (e - s) * 1e-9] for s, e in gaps[:n]]


def merged(ivs):
    out = []
    for s, e in sorted(ivs):
        if e <= s:
            continue
        if out and s <= out[-1][1]:
            out[-1][1] = max(out[-1][1], e)
        else:
            out.append([s, e])
    return out


def own_time(ops) -> list:
    """Split the ops' intervals so that each instant of a device belongs
    to the innermost op running then: a ``while`` op spans the ops of its
    body, which the trace lists too, so summing whole ops would count
    that time twice.  Returns [(start, end, op)] pieces, disjoint on each
    device, whose union is the union of the ops."""
    out = []
    stack: list = []        # [op, the point its own time has reached]

    def pop():
        op, at = stack.pop()
        if op.end > at:
            out.append((at, op.end, op))
        if stack:
            stack[-1][1] = max(stack[-1][1], op.end)

    for o in sorted(ops, key=lambda o: (o.device, o.start, -o.end)):
        while stack and (stack[-1][0].device != o.device
                         or stack[-1][0].end <= o.start):
            pop()
        if stack:
            top = stack[-1]
            if o.start > top[1]:
                out.append((top[1], o.start, top[0]))
            top[1] = max(top[1], o.start)
        stack.append([o, o.start])
    while stack:
        pop()
    return out


#: the opcode of an op named by its HLO text: ``%x = <shape> opcode(``
_OPCODE = re.compile(r"[}\]) ]([a-z][a-z0-9-]*)\(")


def opcode(name: str) -> str:
    """The HLO opcode in an op's HLO text ('' when the name is not HLO)."""
    head, sep, rest = name.partition(" = ")
    if not sep:
        return ""
    m = _OPCODE.search(rest)
    return m.group(1) if m else ""


def classify(name: str) -> str:
    op = opcode(name)
    if op == "sort":
        return "sort"
    if op == "custom-call" and '"tpu_custom_call"' in name:
        return "kernel"
    return "other"


def find_xplane(directory: str) -> str:
    got = sorted(glob.glob(os.path.join(directory, "**", "*.xplane.pb"),
                           recursive=True), key=os.path.getmtime)
    if not got:
        raise FileNotFoundError(f"no .xplane.pb under {directory}")
    return got[-1]


def reduce(path: str) -> Trace:
    """Read one trace file into a ``Trace`` clipped to the window span
    (the whole trace when the span is absent)."""
    from jax.profiler import ProfileData
    pd = ProfileData.from_file(path)
    ops, window = [], None
    devices = []
    for plane in pd.planes:
        if plane.name.startswith(DEVICE_PREFIX):
            try:
                dev = int(plane.name.rsplit(":", 1)[1])
            except ValueError:
                continue
            for line in plane.lines:
                if line.name != OPS_LINE:
                    continue
                devices.append(dev)
                for ev in line.events:
                    s = int(ev.start_ns)
                    e = s + int(ev.duration_ns)
                    ops.append(Op(ev.name, s, e,
                                  classify(ev.name), dev))
            continue
        for line in plane.lines:
            for ev in line.events:
                if ev.name == WINDOW_SPAN:
                    s = int(ev.start_ns)
                    window = (s, s + int(ev.duration_ns))
    if window is None:
        lo = min((o.start for o in ops), default=0)
        hi = max((o.end for o in ops), default=0)
        window = (lo, hi)
    return Trace(window, ops, len(set(devices)))
