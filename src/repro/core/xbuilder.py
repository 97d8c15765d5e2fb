"""XBuilder — accelerator building system (paper §4.3), TPU-adapted.

The paper splits the FPGA die into **Shell** (fixed logic: storage, runtime,
ICAP engine) and **User** (swappable accelerator, programmed as a partial
bitstream through ``Program()``).  On TPU there are no gates to rewire; the
faithful analog is *runtime re-binding of compiled kernels*:

  * **Shell** = the always-present pure-`jnp` C-kernels (device ``"shell"``,
    priority 50) — the framework can always run, like the paper's Shell cores.
  * **User bitstreams** = named kernel sets (e.g. Pallas MXU GEMM = the
    systolic array, Pallas VPU SpMM = the vector processor).  ``program()``
    registers a bitstream's device + kernels into the registry;
    ``unprogram()`` removes it (DFX decoupler).  Reconfiguration time =
    registration + (re)compilation, which we measure and report.

Building blocks (paper Table 2): GEMM, ElementWise, Reduce, SpMM, SDDMM.
"""
from __future__ import annotations

import time
from dataclasses import dataclass, field
from typing import Callable

import jax.numpy as jnp

from .registry import KernelRegistry

SHELL_DEVICE = "shell"
SHELL_PRIORITY = 50


@dataclass
class Bitstream:
    """A 'partial bitfile': a device plus its C-kernel implementations."""
    device: str
    priority: int
    kernels: dict[str, Callable] = field(default_factory=dict)


class XBuilder:
    def __init__(self, registry: KernelRegistry):
        self.registry = registry
        self.loaded: dict[str, Bitstream] = {}
        self.reconfig_log: list[tuple[str, float]] = []
        self._install_shell()

    # ----------------------------------------------------------- Shell logic
    def _install_shell(self) -> None:
        r = self.registry
        r.register_device(SHELL_DEVICE, SHELL_PRIORITY)
        for name, fn in shell_kernels().items():
            r.register_op(name, SHELL_DEVICE, fn)

    # ------------------------------------------------------------ User logic
    def program(self, bitstream: Bitstream) -> float:
        """Paper Program(bitfile): swap in User logic; returns reconfig secs."""
        t0 = time.perf_counter()
        if bitstream.device in self.loaded:
            self.unprogram(bitstream.device)
        self.registry.register_device(bitstream.device, bitstream.priority)
        for op, fn in bitstream.kernels.items():
            self.registry.register_op(op, bitstream.device, fn)
        self.loaded[bitstream.device] = bitstream
        dt = time.perf_counter() - t0
        self.reconfig_log.append((bitstream.device, dt))
        return dt

    def unprogram(self, device: str) -> None:
        if device == SHELL_DEVICE:
            raise ValueError("Shell logic cannot be unprogrammed")
        self.registry.unregister_device(device)
        self.loaded.pop(device, None)


# ----------------------------------------------------------- Shell C-kernels
def shell_kernels() -> dict[str, Callable]:
    """Pure-jnp reference implementations of the Table-2 building blocks plus
    the GNN C-operations used by the paper's DFG example (Fig. 10)."""

    def gemm(a, b):
        return jnp.dot(a, b, preferred_element_type=jnp.float32)

    def spmm(h, nbr, mask, *, mode: str = "mean"):
        # ELL/page-format aggregation: h (N,F); nbr,mask (D,K) -> (D,F)
        g = jnp.take(h, nbr, axis=0) * mask[..., None]
        s = g.sum(axis=1)
        if mode == "sum":
            return s
        deg = jnp.maximum(mask.sum(axis=1), 1.0)
        return s / deg[:, None]

    def sddmm(h, nbr, mask):
        # per-edge elementwise product with the destination row (NGCF term):
        # out[i,k,:] = h[i,:] * h[nbr[i,k],:]        (D,K,F)
        g = jnp.take(h, nbr, axis=0)
        d = h[: nbr.shape[0]]
        return g * d[:, None, :] * mask[..., None]

    def elementwise(x, y=None, *, op: str = "relu"):
        if op == "relu":
            return jnp.maximum(x, 0.0)
        if op == "add":
            return x + y
        if op == "mul":
            return x * y
        raise ValueError(op)

    def reduce_(x, *, axis: int = 1, op: str = "sum"):
        if op == "sum":
            return x.sum(axis=axis)
        if op == "mean":
            return x.mean(axis=axis)
        if op == "max":
            return x.max(axis=axis)
        raise ValueError(op)

    def bias_add(x, b):
        return x + b[None, :]

    return {
        "GEMM": gemm,
        "SpMM": spmm,
        "SpMM_Mean": lambda h, nbr, mask: spmm(h, nbr, mask, mode="mean"),
        "SpMM_Sum": lambda h, nbr, mask: spmm(h, nbr, mask, mode="sum"),
        "SDDMM": sddmm,
        "ElementWise": elementwise,
        "ReLU": lambda x: elementwise(x, op="relu"),
        "Add": lambda x, y: elementwise(x, y, op="add"),
        "Mul": lambda x, y: elementwise(x, y, op="mul"),
        "Reduce": reduce_,
        "BiasAdd": bias_add,
        "Scale": lambda x, s: x * s,
    }
