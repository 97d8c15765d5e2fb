"""Rows of the layer-0 feature table after bucket padding over the rows
before it: the ``serve.rows_padded`` counter over ``serve.rows_real``,
each as its change across the window (``stats`` RPC ``spans``)."""
from bench.spans import change


def read(ctx):
    real, padded = (change(ctx, "serve.rows_real"),
                    change(ctx, "serve.rows_padded"))
    if real is None or padded is None or real["n"] <= 0:
        return None
    return padded["n"] / real["n"]
