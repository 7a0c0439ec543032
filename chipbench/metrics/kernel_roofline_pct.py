"""kernel_roofline_pct: the least time the chip's HBM needs for the bytes
the window's aggregations need, over the device time of the kernel
(custom-call ops) inside the window, in percent.  The bytes come from
the plans (chipbench/roofline.py): each coalesced launch reads its input
once, each request writes its result."""
from chipbench.roofline import min_seconds


def read(run):
    tr = run.trace
    if tr is None:
        return None
    k = tr.category_s("kernel")
    if k <= 0:
        return None
    nbytes = sum(run.bytes_per_launch[p] for p, _g in run.launches) + sum(
        run.bytes_per_result[p] * len(g) for p, g in run.launches)
    return 100.0 * min_seconds(nbytes, run.device_kind) / k
