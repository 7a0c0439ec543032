"""sort_ms: device milliseconds of the trace's ``sort`` ops inside the
window, per request completed in it: the one ``lax.sort`` of
``Table.sort_by`` (the group sort of the sorted route), without the row
gathers that apply its permutation (those are in ``other_ops_ms``)."""


def read(run):
    tr, done = run.trace, run.requests()
    if tr is None or not done:
        return None
    s = tr.category_s("sort")
    return s * 1e3 / len(done) if s > 0 else None
