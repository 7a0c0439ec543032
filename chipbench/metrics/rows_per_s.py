"""rows_per_s: input rows of every request completed in the window, over
the window (host clock).  A request's input rows are the valid rows of
the tables its plan scans."""


def read(run):
    done = run.requests()
    if not done:
        return None
    return sum(run.rows[id(r)] for r in done) / run.window_s
