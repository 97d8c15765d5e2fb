"""Bytes a fused group hands to the device, in MB (1e6 B): the
``serve.h2d_bytes`` counter (the padded feature table and each layer's
neighbour and mask blocks put on the device in ``serve.h2d``) over the
groups run, each as its change across the window (``stats`` RPC
``spans``).  None where the program keeps no such counter."""
from bench.metrics.embed_mb import mb_per_group


def read(ctx):
    return mb_per_group(ctx, "serve.h2d_bytes")
