"""Host wall time of a fused group's engine call, in ms: the seconds of
its ``engine.run`` span (dispatch, device execution and the wait for the
results) over the groups run, each as its change across the window
(``stats`` RPC ``spans``).  ``mfu`` keeps the device time."""
from bench.spans import per_group_ms


def read(ctx):
    return per_group_ms(ctx, "engine.run")
