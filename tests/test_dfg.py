"""GraphRunner: DFG topo-sort/serialization, registry priority dispatch,
XBuilder program/unprogram semantics (Table 3 behaviour)."""
import numpy as np
import jax.numpy as jnp
import pytest

from repro.core.dfg import DFG, Engine
from repro.core.registry import KernelRegistry
from repro.core.xbuilder import XBuilder, Bitstream
from repro.core import gnn
from repro.kernels.ops import program_config


def test_markup_roundtrip_and_topo():
    g = DFG()
    a = g.create_in("A")
    b = g.create_in("B")
    (c,) = g.create_op("Add", [a, b])
    (d,) = g.create_op("Mul", [c, a])
    g.create_out("Out", d)
    g2 = DFG.load(g.save())
    order = [n.op for n in g2.topo_nodes()]
    assert order == ["Add", "Mul"]

    reg = KernelRegistry()
    reg.register_device("cpu", 50)
    reg.register_op("Add", "cpu", lambda x, y: x + y)
    reg.register_op("Mul", "cpu", lambda x, y: x * y)
    out = Engine(reg).run(g2, {"A": 3.0, "B": 4.0})
    assert out["Out"] == 21.0


def test_cycle_detection():
    g = DFG()
    a = g.create_in("A")
    (b,) = g.create_op("Add", [a, "2_0"])       # forward ref -> cycle
    (c,) = g.create_op("Mul", [b, b])
    g._nodes[1].inputs = [str(b), "1_0"]        # self-loop
    with pytest.raises(ValueError):
        g.topo_nodes()


def test_priority_dispatch_and_reconfig():
    reg = KernelRegistry()
    xb = XBuilder(reg)                          # installs Shell (shell, 50)
    calls = []

    def mk(dev):
        def f(a, b):
            calls.append(dev)
            return jnp.dot(a, b)
        return f

    xb.program(Bitstream("vector", 150, {"GEMM": mk("vector")}))
    xb.program(Bitstream("systolic", 300, {"GEMM": mk("systolic")}))
    dev, fn = reg.resolve("GEMM")
    assert dev == "systolic"                    # highest priority wins
    a = jnp.ones((4, 4))
    reg.dispatch("GEMM", a, a)
    assert calls == ["systolic"]

    xb.unprogram("systolic")                    # DFX decoupler
    dev, _ = reg.resolve("GEMM")
    assert dev == "vector"
    xb.unprogram("vector")
    dev, _ = reg.resolve("GEMM")
    assert dev == "shell"                       # Shell always present
    with pytest.raises(ValueError):
        xb.unprogram("shell")


def test_named_configs_match_shell():
    """Octa/Lsap/Hetero all compute the same GNN result (Fig. 16 setup)."""
    rng = np.random.default_rng(0)
    h = jnp.asarray(rng.standard_normal((64, 32)), jnp.float32)
    nbr = jnp.asarray(rng.integers(0, 64, (16, 5)), jnp.int32)
    mask = jnp.asarray(rng.integers(0, 2, (16, 5)), jnp.float32)

    results = {}
    for name in ("octa", "lsap", "hetero"):
        reg = KernelRegistry()
        xb = XBuilder(reg)
        program_config(xb, name)
        results[name] = np.asarray(reg.dispatch("SpMM_Mean", h, nbr, mask))
    np.testing.assert_allclose(results["octa"], results["lsap"],
                               rtol=1e-5, atol=1e-5)
    np.testing.assert_allclose(results["octa"], results["hetero"],
                               rtol=1e-5, atol=1e-5)


def test_gnn_dfg_equals_direct():
    from repro.core.service import HolisticGNNService, make_service_dfg
    import repro.store.sampler as S
    rng = np.random.default_rng(3)
    edges = np.stack([rng.integers(0, 80, 400), rng.integers(0, 80, 400)],
                     axis=1).astype(np.int64)
    emb = rng.standard_normal((80, 24)).astype(np.float32)
    svc = HolisticGNNService(h_threshold=8, pad_to=16)
    svc.update_graph(edges, emb)
    for model in ("gcn", "gin", "ngcf"):
        params = gnn.init_params(model, [24, 12, 8], seed=2)
        dfg = make_service_dfg(model, 2, [4, 4])
        weights = gnn.dfg_feeds(model, params, None, [])
        weights.pop("H")
        out = svc.run(dfg.save(), [1, 2], weights=weights)["Result"]
        b = S.sample_batch(svc.store, [1, 2], [4, 4],
                           rng=np.random.default_rng(0), pad_to=16)
        blocks = [(jnp.asarray(x.nbr), jnp.asarray(x.mask)) for x in b.layers]
        ref = gnn.FORWARD[model](params, jnp.asarray(b.embeddings), blocks)
        np.testing.assert_allclose(out[:2], np.asarray(ref)[:2],
                                   rtol=2e-5, atol=2e-5)
