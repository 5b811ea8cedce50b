"""Share of the roofline reached by the grouped (depthwise) RSS
matrix-product kernel (``_grouped_shared_call``): the least time of its
launches in the traced window (``bench/work.py``) over their device
time."""

KERNEL = "_grouped_shared_call"
PARTS = ("depthwise",)


def read(run):
    return run.work.roofline_percent(run, KERNEL, PARTS)
