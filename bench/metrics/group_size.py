"""Requests per fused group over the window, from the scheduler's QoS
counters in the ``stats`` RPC: requests grouped (``avg_group_size`` times
``groups``) over groups run, each as its change across the window."""


def read(ctx):
    b, a = ctx["before"]["qos"], ctx["after"]["qos"]
    groups = a["groups"] - b["groups"]
    if groups <= 0:
        return None
    grouped = round(a["avg_group_size"] * a["groups"]) \
        - round(b["avg_group_size"] * b["groups"])
    return grouped / groups
