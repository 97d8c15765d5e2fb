"""jit'd kernel wrappers + the paper's User-logic "bitstreams".

Three accelerator configurations mirror the paper's prototypes (Fig. 12):

  * **Octa-HGNN**  — software-only: every C-kernel is the Shell jnp path
    (registering Octa is a no-op bitstream; it exists so the Fig. 16
    comparison has the same dispatch machinery).
  * **Lsap-HGNN**  — a large systolic array only: GEMM goes to the Pallas
    MXU kernel, but the irregular aggregation (SpMM/SDDMM) has *no* vector
    unit and is forced through GEMM-style dense ops (one-hot matmul) — the
    paper's "systolic arrays cannot traverse graphs" effect.
  * **Hetero-HGNN** — vector + systolic: SpMM/SDDMM on the VPU kernels,
    GEMM on the MXU kernel (highest priority), the winning configuration.

Every kernel follows the backend (``kernels/config.py``): Mosaic compiles
it on a TPU, and the Pallas interpreter runs the same ``pallas_call`` on the
CPU.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from ..core.xbuilder import Bitstream
from .gemm import gemm
from .spmm import spmm
from .sddmm import sddmm
from .rmsnorm import rmsnorm
from .agg_combine import agg_combine, agg_combine_partial
from .flash_attention import flash_attention
from .decode_attention import decode_attention


# ----------------------------------------------------------- dense fallbacks
def _spmm_via_gemm(h, nbr, mask, *, mode: str = "mean"):
    """Lsap path: aggregation lowered onto the systolic array as a dense
    one-hot matmul — correct but wasteful (the paper's Fig. 16 point)."""
    n = h.shape[0]
    d, k = nbr.shape
    onehot = jax.nn.one_hot(nbr, n, dtype=h.dtype) * mask[..., None]  # (D,K,N)
    a = onehot.sum(axis=1)                                            # (D,N)
    if mode == "mean":
        deg = jnp.maximum(mask.sum(axis=1), 1.0)
        a = a / deg[:, None]
    return gemm(a, h)


def _sddmm_via_gemm(h, nbr, mask):
    n = h.shape[0]
    d, k = nbr.shape
    onehot = jax.nn.one_hot(nbr.reshape(-1), n, dtype=h.dtype)        # (D*K,N)
    g = gemm(onehot, h).reshape(d, k, -1)
    return g * h[:d][:, None, :] * mask[..., None]


# ------------------------------------------------------------- bitstreams
def octa_bitstream() -> Bitstream:
    return Bitstream(device="octa-o3", priority=60, kernels={})


def lsap_bitstream() -> Bitstream:
    return Bitstream(device="systolic", priority=300, kernels={
        "GEMM": lambda a, b: gemm(a, b),
        "SpMM": functools.partial(_spmm_via_gemm),
        "SpMM_Mean": lambda h, n, m: _spmm_via_gemm(h, n, m, mode="mean"),
        "SpMM_Sum": lambda h, n, m: _spmm_via_gemm(h, n, m, mode="sum"),
        "SDDMM": _sddmm_via_gemm,
    })


def hetero_bitstream() -> Bitstream:
    bs = Bitstream(device="vector", priority=150, kernels={
        "SpMM": lambda h, n, m, mode="mean": spmm(h, n, m, mode=mode),
        "SpMM_Mean": lambda h, n, m: spmm(h, n, m, mode="mean"),
        "SpMM_Sum": lambda h, n, m: spmm(h, n, m, mode="sum"),
        "SDDMM": lambda h, n, m: sddmm(h, n, m),
        "RMSNorm": lambda x, w: rmsnorm(x, w),
        # fused aggregate-combine: one whole GCN layer per kernel launch —
        # the engine's fusion pass targets this C-operation when present.
        "AggCombine": lambda h, n, m, w, b: agg_combine(h, n, m, w, b,
                                                        mode="mean"),
        # slice-shaped SPMD entry: agg@w partial product, no epilogue —
        # the sharded engine psums this across the model axis before
        # applying bias+relu to the full sum.
        "AggCombinePartial": lambda h, n, m, w: agg_combine_partial(
            h, n, m, w, mode="mean"),
    })
    return bs


def hetero_gemm_bitstream() -> Bitstream:
    """The systolic half of Hetero (program both this and hetero_bitstream)."""
    return Bitstream(device="systolic", priority=300, kernels={
        "GEMM": lambda a, b: gemm(a, b),
    })


BITSTREAMS = {
    "octa": [octa_bitstream],
    "lsap": [lsap_bitstream],
    "hetero": [hetero_bitstream, hetero_gemm_bitstream],
}


def program_config(xbuilder, name: str) -> float:
    """Program a named accelerator configuration; returns reconfig seconds."""
    for dev in list(xbuilder.loaded):
        xbuilder.unprogram(dev)
    total = 0.0
    for mk in BITSTREAMS[name]:
        total += xbuilder.program(mk())
    return total


__all__ = ["gemm", "spmm", "sddmm", "rmsnorm", "agg_combine",
           "agg_combine_partial",
           "flash_attention", "decode_attention", "BITSTREAMS",
           "program_config",
           "octa_bitstream", "lsap_bitstream", "hetero_bitstream",
           "hetero_gemm_bitstream"]
