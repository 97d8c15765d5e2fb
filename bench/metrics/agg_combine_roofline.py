"""AggCombine's share of its roofline, in percent.

The least time the chip could take for the work the calls needed, the
larger of their operations over the bf16 peak and their bytes over the HBM
bandwidth, over the summed time of the kernel's executions in the trace.
Operations and bytes are counted from the real rows and live slots as for
``mfu``; each call reads its layer's weights once.  The run prints which
bound applies."""


def read(ctx):
    tr, work = ctx["trace"], ctx["work"]
    if tr is None or work is None or tr["kernel_s"] <= 0:
        return None
    return 100.0 * work["kernel_min_s"] / tr["kernel_s"]
