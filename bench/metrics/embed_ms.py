"""Host time of a fused group's embedding gather, in ms: the seconds of
its ``store.embed`` span over the groups run, each as its change across
the window (``stats`` RPC ``spans``)."""
from bench.spans import per_group_ms


def read(ctx):
    return per_group_ms(ctx, "store.embed")
