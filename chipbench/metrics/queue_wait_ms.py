"""queue_wait_ms: mean milliseconds a request waited in the server's
admission queue, from ``submit`` to the dispatcher taking it for a
launch: the change of ``ServeStats.queue_wait_s`` over the change of
``ServeStats.requests`` between the window's open and close."""


def read(run):
    s0, s1 = run.stats0, run.stats1
    if s0 is None or s1 is None:
        return None
    w0 = getattr(s0, "queue_wait_s", None)
    w1 = getattr(s1, "queue_wait_s", None)
    n = s1.requests - s0.requests
    if w0 is None or w1 is None or n <= 0:
        return None
    return (w1 - w0) * 1e3 / n
