"""Mean wait of a request in the scheduler's queue, from its enqueue to
the start of its fused group, in ms: the ``serve.queue_wait`` counter's
seconds over its count, each as its change across the window (``stats``
RPC ``spans``)."""
from bench.spans import change


def read(ctx):
    q = change(ctx, "serve.queue_wait")
    if q is None or q["n"] <= 0:
        return None
    return 1e3 * q["s"] / q["n"]
