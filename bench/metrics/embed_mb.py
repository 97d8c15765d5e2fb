"""Host bytes a fused group's embedding gather writes, in MB (1e6 B): the
``store.embed_bytes`` counter (rows x d x 4, plus 4096 per page the page
cache installs) over the groups run, each as its change across the window
(``stats`` RPC ``spans``).  None where the program keeps no such
counter."""
from bench.spans import change


def read(ctx):
    return mb_per_group(ctx, "store.embed_bytes")


def mb_per_group(ctx, name):
    groups = change(ctx, "serve.group")
    if groups is None or groups["n"] <= 0 or name not in ctx["after"]["spans"]:
        return None
    return change(ctx, name)["n"] / 1e6 / groups["n"]
