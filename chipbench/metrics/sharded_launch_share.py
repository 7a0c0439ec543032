"""sharded_launch_share: the share of the window's launches whose grouped
node sorted a row-split table shard by shard: the change of
``ServeStats.sharded_launches`` over the change of ``ServeStats.batches``
between the window's open and close.  1.0 when every launch took the
shard-local route; less when a launch sorted every row in one sort."""


def read(run):
    s0, s1 = run.stats0, run.stats1
    if s0 is None or s1 is None:
        return None
    a0 = getattr(s0, "sharded_launches", None)
    a1 = getattr(s1, "sharded_launches", None)
    batches = s1.batches - s0.batches
    if a0 is None or a1 is None or batches <= 0:
        return None
    return (a1 - a0) / batches
