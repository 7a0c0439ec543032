"""collective_ms: device milliseconds of the cross-chip collectives inside
the window, per request completed in it, averaged over the chips: the
own time (``trace_reduce.own_time``) of every op whose HLO opcode
(``trace_reduce.opcode``) is an all-reduce, all-gather, reduce-scatter,
all-to-all or collective-permute, or the ``-start``/``-done`` half of
one.  On the shard-local sorted route these are the merge of the
partial groups (scope ``shard_merge``)."""
from chipbench.trace_reduce import opcode

COLLECTIVES = ("all-reduce", "all-gather", "reduce-scatter", "all-to-all",
               "collective-permute")
OPCODES = frozenset(c + s for c in COLLECTIVES for s in ("", "-start",
                                                        "-done"))


def read(run):
    tr, done = run.trace, run.requests()
    if tr is None or not done or not tr.devices:
        return None
    lo, hi = tr.window
    ns = sum(max(0, min(e, hi) - max(s, lo))
             for s, e, o in tr._own() if opcode(o.name) in OPCODES)
    if ns <= 0:
        return None
    return ns * 1e-6 / tr.devices / len(done)
