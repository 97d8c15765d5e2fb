"""Host time of a fused group's feeds, in ms: bucket padding
(``serve.pad``) and the host-to-device transfers of the padded table and
blocks (``serve.h2d``) over the groups run, each as its change across the
window (``stats`` RPC ``spans``).  A transfer still in flight when the
program is dispatched is waited for inside ``engine.run``."""
from bench.spans import per_group_ms


def read(ctx):
    return per_group_ms(ctx, "serve.pad", "serve.h2d")
