"""reqs_per_launch: requests over launches the server counted
(``ServeStats.requests / batches``) between the window's open and close."""


def read(run):
    if run.stats0 is None or run.stats1 is None:
        return None
    batches = run.stats1.batches - run.stats0.batches
    if batches <= 0:
        return None
    return (run.stats1.requests - run.stats0.requests) / batches
