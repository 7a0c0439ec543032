"""The one traffic generator: turns a traffic file into request schedules
and drives them against the server for one measured window.

A traffic file (``chipbench/traffic/<name>.json``) lists closed-loop
streams: ``clients`` threads, each sending its next request when the last
one returned.  A client walks a ``cycle`` of requests from the offset
``client * stride``, or a sequence drawn from the seed out of a ``mix``
(each entry's ``share`` of ``length`` requests, exact counts in a seeded
order; parameters given as ``{"int_uniform": [lo, hi]}`` are drawn from
the seed).  A request names a plan of the configuration, its parameters
and the ``consistency`` it is read at.

Every seed gives each client the same set of requests, so seeds change
the order and the data, not the amount of work.
"""
from __future__ import annotations

import threading
import time
from dataclasses import dataclass, field
from typing import Any, Callable, Optional

import numpy as np


@dataclass
class Request:
    plan: str
    params: dict
    consistency: str = "latest"


@dataclass
class Record:
    """One request of the window (host clock, seconds)."""
    stream: str
    plan: str
    params: dict
    start: float = 0.0
    done: Optional[float] = None
    error: Optional[str] = None
    got: Any = None             # host copy of the result's columns
    consistency: str = "latest"


def _param(spec, rng):
    if isinstance(spec, dict) and "int_uniform" in spec:
        lo, hi = spec["int_uniform"]
        return int(rng.integers(lo, hi + 1))
    return spec


def client_sequences(stream: dict, rng: np.random.Generator) -> list:
    """One list of ``Request`` per client of a closed stream."""
    if stream.get("type", "closed") != "closed":
        raise ValueError(f"stream {stream.get('name')!r}: only closed-loop "
                         f"streams are generated, not {stream['type']!r}")
    cons = stream.get("consistency", "latest")
    n = int(stream["clients"])
    if "cycle" in stream:
        cyc = [Request(r["plan"], dict(r.get("params", {})),
                       r.get("consistency", cons)) for r in stream["cycle"]]
        stride = int(stream.get("stride", 1))
        return [cyc[(c * stride) % len(cyc):] + cyc[:(c * stride) % len(cyc)]
                for c in range(n)]
    mix, length = stream["mix"], int(stream["length"])
    out = []
    for _c in range(n):
        seq = []
        for r in mix:
            k = int(round(r["share"] * length))
            seq += [Request(r["plan"], {p: _param(v, rng) for p, v in
                                        r.get("params", {}).items()},
                            r.get("consistency", cons)) for _ in range(k)]
        out.append([seq[i] for i in rng.permutation(len(seq))])
    return out


@dataclass
class Window:
    """Shared state of one measured window."""
    seconds: float
    t0: float = 0.0
    close_at: float = 0.0           # set when the window closes
    closed: threading.Event = field(default_factory=threading.Event)
    lock: threading.Lock = field(default_factory=threading.Lock)
    records: list = field(default_factory=list)

    def completed(self, t: float) -> None:
        """A request finished at ``t``: the window closes at the first
        completion at or after its length."""
        if t >= self.t0 + self.seconds and not self.closed.is_set():
            with self.lock:
                if not self.closed.is_set():
                    self.close_at = t
                    self.closed.set()


def run_window(streams: list, seconds: float, send: Callable,
               consume: Callable, on_open: Callable = lambda w: None,
               give_up_s: float = 240.0) -> tuple:
    """Drive every stream for one window.  ``send(Request)`` serves one
    request and returns when its answer is there; ``consume(Request,
    answer) -> host copy`` is what the client then does with it, after
    its latency is taken.  ``streams`` holds (stream dict, client
    sequences) pairs.  ``on_open(window)`` runs just before the first
    request is sent.  Returns the window, closed, and the threads still
    finishing what they sent."""
    win = Window(seconds)
    threads = []

    def client(name, seq):
        i = 0
        while not win.closed.is_set():
            req = seq[i % len(seq)]
            i += 1
            rec = Record(name, req.plan, req.params,
                         consistency=req.consistency)
            rec.start = time.perf_counter()
            try:
                answer = send(req)
                rec.done = time.perf_counter()
                rec.got = consume(req, answer)
            except Exception as e:      # noqa: BLE001 — counted as failed
                rec.error = f"{type(e).__name__}: {e}"
                rec.done = rec.done or time.perf_counter()
            with win.lock:
                win.records.append(rec)
            win.completed(rec.done)

    for stream, sched in streams:
        for c, seq in enumerate(sched):
            threads.append(threading.Thread(
                target=client, args=(stream["name"], seq),
                name=f"chipbench-{stream['name']}-{c}", daemon=True))
    on_open(win)
    win.t0 = time.perf_counter()
    for th in threads:
        th.start()
    if not win.closed.wait(seconds + give_up_s):
        win.close_at = time.perf_counter()
        win.closed.set()
    return win, threads
