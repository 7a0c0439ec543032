"""other_ops_ms: device milliseconds of the trace's ops that are neither
a ``sort`` nor the Pallas kernel, each counted for its own time
(``trace_reduce.own_time``), inside the window, per request completed in
it.  On the sorted route most of it is the row gathers that
apply the group sort's permutation to every column (``Table.take`` after
``lax.sort``); the rest is joins, masks and result compaction."""


def read(run):
    tr, done = run.trace, run.requests()
    if tr is None or not done:
        return None
    s = tr.category_s("other")
    return s * 1e3 / len(done) if s > 0 else None
