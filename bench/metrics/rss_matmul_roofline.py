"""Share of the roofline reached by the dense RSS matrix-product kernel
(``_rss_matmul_call``: convolutions, pointwise convolutions, fully
connected layers): the least time of its launches in the traced window
(``bench/work.py``) over their device time."""

KERNEL = "_rss_matmul_call"
PARTS = ("conv", "pointwise", "fc")


def read(run):
    return run.work.roofline_percent(run, KERNEL, PARTS)
