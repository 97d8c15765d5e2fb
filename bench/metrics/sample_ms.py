"""Host time of a fused group's neighbour sampling, in ms: the seconds of
its ``store.hop`` spans (plan, page read, selection and reindex of every
hop) over the groups run, each as its change across the window (``stats``
RPC ``spans``)."""
from bench.spans import per_group_ms


def read(ctx):
    return per_group_ms(ctx, "store.hop")
