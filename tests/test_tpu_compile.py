"""The served path's Pallas kernels and the engine's jitted GCN step compile
for a TPU v5e (described, not attached) at the largest super-batch bucket the
continuous batcher emits, with F=420, the widest feature table served.

The topology and every sharding built from it live in module-scoped
fixtures: describing the topology loads the TPU compiler library, which one
process at a time may hold.  The persistent compilation cache is off around
the compiles (an entry written for a described chip cannot be read back).
"""
import functools

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from repro.core.service import HolisticGNNService, make_service_dfg
from repro.kernels import config
from repro.kernels.agg_combine import agg_combine, agg_combine_partial
from repro.kernels.gemm import gemm
from repro.kernels.ops import program_config
from repro.kernels.sddmm import sddmm
from repro.kernels.spmm import spmm
from repro.serve.batcher import _bucket, split_service_dfg

# the serving runtime's full group: 16 requests x 8 targets, fanouts
# [10, 10], bucketed on the service's pad_to
MAX_GROUP, TARGETS, FANOUTS, PAD_TO = 16, 8, (10, 10), 64
F, HIDDEN = 420, 256
D1 = MAX_GROUP * TARGETS
D0 = D1 * (1 + FANOUTS[0])
N = _bucket(D0 * (1 + FANOUTS[1]), PAD_TO)
D0, D1 = _bucket(D0, PAD_TO), _bucket(D1, PAD_TO)
K = FANOUTS[0]


@pytest.fixture(scope="module")
def topo():
    from jax.experimental import topologies
    try:
        return topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:  # noqa: BLE001 — no TPU compiler here
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")


@pytest.fixture(scope="module")
def one_chip(topo):
    return SingleDeviceSharding(topo.devices[0])


@pytest.fixture
def no_cache():
    from jax.experimental.compilation_cache import compilation_cache
    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield
    jax.config.update("jax_enable_compilation_cache", was)
    compilation_cache.reset_cache()


def _compiled_text(fn, *args) -> str:
    return jax.jit(fn).lower(*args).compile().as_text()


KERNELS = {
    "spmm": (functools.partial(spmm, mode="mean", interpret=False),
             ["h", "nbr", "mask"]),
    "sddmm": (functools.partial(sddmm, interpret=False),
              ["h", "nbr", "mask"]),
    "agg_combine": (functools.partial(agg_combine, interpret=False),
                    ["h", "nbr", "mask", "w", "b"]),
    "agg_combine_partial": (functools.partial(agg_combine_partial,
                                              interpret=False),
                            ["h", "nbr", "mask", "w"]),
    "gemm": (functools.partial(gemm, interpret=False), ["x", "w"]),
}


@pytest.mark.parametrize("name", sorted(KERNELS))
def test_kernel_compiles_for_v5e(name, one_chip, no_cache):
    shapes = {"h": ((N, F), jnp.float32), "nbr": ((D0, K), jnp.int32),
              "mask": ((D0, K), jnp.float32), "w": ((F, HIDDEN), jnp.float32),
              "b": ((HIDDEN,), jnp.float32), "x": ((D0, F), jnp.float32)}
    fn, argnames = KERNELS[name]
    args = [jax.ShapeDtypeStruct(*shapes[a], sharding=one_chip)
            for a in argnames]
    assert "tpu_custom_call" in _compiled_text(fn, *args)


def test_engine_gcn_step_compiles_for_v5e(one_chip, no_cache, monkeypatch):
    # JAX_PLATFORMS=cpu resolves kernels to interpret mode; the step is
    # compiled as the TPU backend would resolve it
    monkeypatch.setattr(config, "default_interpret", lambda: False)
    svc = HolisticGNNService(pad_to=PAD_TO)
    program_config(svc.xbuilder, "hetero")
    prog = split_service_dfg(make_service_dfg("gcn", 2, list(FANOUTS)))
    h, nbr0, mask0, nbr1, mask1 = prog.feed_refs
    shapes = {h: ((N, F), jnp.float32),
              nbr0: ((D0, K), jnp.int32), mask0: ((D0, K), jnp.float32),
              nbr1: ((D1, K), jnp.int32), mask1: ((D1, K), jnp.float32),
              "W0": ((F, HIDDEN), jnp.float32), "b0": ((HIDDEN,), jnp.float32),
              "W1": ((HIDDEN, HIDDEN), jnp.float32),
              "b1": ((HIDDEN,), jnp.float32)}
    feeds = {r: jax.ShapeDtypeStruct(*s, sharding=one_chip)
             for r, s in shapes.items()}
    fn, refs = svc.engine.jit_program(prog.model, feeds)
    text = fn.lower(*(feeds[r] for r in refs)).compile().as_text()
    assert text.count("tpu_custom_call") >= 2      # both fused GCN layers
    svc.close()
