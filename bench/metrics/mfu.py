"""The model step's share of the chip's bf16 peak, in percent.

Operations the served requests needed, counted from their real (unpadded)
sampled rows and live neighbour slots by ``models/<model>.py``, over the
summed device time of the engine's program executions in the trace, over
the peak.  The trace spans the whole window, so both sides cover the same
executions."""


def read(ctx):
    tr, work = ctx["trace"], ctx["work"]
    if tr is None or work is None or tr["module_s"] <= 0:
        return None
    return 100.0 * work["flops"] / (tr["module_s"]
                                    * ctx["peaks"]["bf16_flops_per_s"])
