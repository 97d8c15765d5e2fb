"""Bring-up check: the served GNN path runs end to end on a TPU.

  python chip_smoke.py [--seed 0]     # one chip: GCN, GIN and NGCF served
  python chip_smoke.py --chips 4      # the SPMD engine on a 4-chip mesh,
                                      # compared with the one-chip engine

The graph has the ``physics`` row's shape of ``benchmarks/common.py``
(12,000 vertices, 90,000 power-law edges, 420 features), generated from
``--seed`` and bulk-ingested with ``update_graph`` over the RPC client.  A
``ServingRuntime`` (continuous batcher, ``max_group=16``) serves every model
through the Hetero bitstream's compiled Pallas kernels, with widths
[420, 256, 256] and fanouts [10, 10]:

  * GCN: 16 concurrent clients x 8 requests of 8 targets;
  * GIN and NGCF: 4 clients x 2 requests each;
  * each of client 0's answers is compared with the float32 ``jnp`` forward
    (``core/gnn.py`` ``FORWARD``) on the same sampled batch;
  * the engine trace must show GCN's fused ``AggCombine`` and NGCF's
    ``SDDMM`` on the ``vector`` device, and no operation that the Hetero
    bitstream implements on the ``shell``.

``--chips 4`` serves the same GCN traffic from a ``model_parallel=4``
service and compares every answer with the one-chip engine run of the same
request; it runs nothing else.

One process drives every phase and holds the chip; nothing here starts a
JAX child.  The script refuses to run without a TPU.  A failed check
raises, so the script then exits non-zero without the result line.  The
last line of stdout is
``{"ok": true, "device": {"platform": "tpu", "kind": ..., "count": ...}}``.
"""
from __future__ import annotations

import argparse
import json
import os
import sys
import threading
import time

import numpy as np

VERTICES, EDGES, FEATURES = 12_000, 90_000, 420     # benchmarks "physics"
WIDTHS = [FEATURES, 256, 256]
FANOUTS = [10, 10]
TARGETS = 8
# Limits on an answer's largest error, as a share of its largest value.
# The reference contracts at fp32; Mosaic contracts f32 operands in one
# bf16 MXU pass by default, as XLA's default precision does, which after
# GIN's four matmuls leaves errors of a fraction of a percent.  A wrong row
# or mask moves an answer by O(its scale).
REF_REL = 2e-2
# SPMD and one-chip runs share kernels and precision, but the psum order of
# the partial products differs, and a hidden value that lands on the other
# side of a bf16 rounding step moves the next layer by about 2^-9 of one
# term.
SPMD_REL = 2e-3


def log(msg: str) -> None:
    print(msg, flush=True)


def make_graph(seed: int):
    """Power-law graph (the generator of ``benchmarks/common.py``)."""
    rng = np.random.default_rng(seed)
    src = rng.zipf(1.35, EDGES) % VERTICES
    dst = rng.integers(0, VERTICES, EDGES)
    edges = np.stack([dst, src], axis=1).astype(np.int64)
    emb = rng.standard_normal((VERTICES, FEATURES)).astype(np.float32)
    return edges, emb


def start_service(edges, emb, *, model_parallel=None):
    from repro.core.service import HolisticGNNService
    from repro.kernels.ops import program_config
    from repro.serve import ServingRuntime

    svc = HolisticGNNService(h_threshold=64, pad_to=64, cache_pages=4096,
                             model_parallel=model_parallel)
    runtime = ServingRuntime(svc, n_queues=8, max_group=16, max_pending=512)
    runtime.start()
    boot = runtime.client()
    boot.call("update_graph", edge_array=edges, embeddings=emb, timeout=600)
    program_config(svc.xbuilder, "hetero")
    return svc, runtime, boot


def deploy(boot, model: str, seed: int):
    from repro.core import gnn
    from repro.core.service import make_service_dfg

    params = gnn.init_params(model, WIDTHS, seed=seed + 1)
    weights = {k: np.asarray(v) for k, v in
               gnn.dfg_feeds(model, params, None, []).items() if k != "H"}
    boot.call("put_weights", name=model, weights=weights, timeout=600)
    dfg = make_service_dfg(model, len(FANOUTS), FANOUTS).save()
    return params, weights, dfg


def serve(runtime, model: str, dfg: str, *, clients: int, requests: int,
          seed: int) -> dict:
    """Concurrent clients; returns {(client, req): (targets, seed, rows)}."""
    answers: dict = {}
    errors: list = []
    lock = threading.Lock()

    def client(c: int) -> None:
        cl = runtime.client()
        rng = np.random.default_rng([seed, c])
        for r in range(requests):
            targets = rng.integers(0, VERTICES, TARGETS).tolist()
            rseed = c * 1000 + r
            try:
                out = cl.call("run", dfg=dfg, batch=targets, weights_ref=model,
                              seed=rseed, timeout=600)
            except Exception as e:  # noqa: BLE001 — re-raised below
                with lock:
                    errors.append(f"{model} client {c} request {r}: {e!r}")
                return
            with lock:
                answers[(c, r)] = (targets, rseed, np.asarray(out["Result"]))

    threads = [threading.Thread(target=client, args=(c,))
               for c in range(clients)]
    for t in threads:
        t.start()
    for t in threads:
        t.join()
    if errors:
        raise RuntimeError(f"{len(errors)} failed requests; first: "
                           f"{errors[0]}")
    if len(answers) != clients * requests:
        raise RuntimeError(f"{model}: {len(answers)} answers for "
                           f"{clients * requests} requests")
    for (c, r), (targets, _, rows) in answers.items():
        if rows.shape != (len(targets), WIDTHS[-1]) \
                or not np.isfinite(rows).all():
            raise RuntimeError(f"{model} answer {(c, r)}: shape {rows.shape}"
                               " or non-finite values")
    return answers


def reference(store, model: str, params, targets, rseed):
    """float32 ``jnp`` forward on the batch the service sampled."""
    import jax
    import jax.numpy as jnp
    from repro.core import gnn
    from repro.serve.batcher import sample_group

    batch, _ = sample_group(store, [targets], [rseed], FANOUTS)
    blocks = [(jnp.asarray(b.nbr), jnp.asarray(b.mask)) for b in batch.layers]
    with jax.default_matmul_precision("float32"):
        out = gnn.FORWARD[model](params, jnp.asarray(batch.embeddings), blocks)
    return np.asarray(out)


def relative_error(rows, ref, limit: float, what: str) -> float:
    """Largest |rows - ref| over the largest |ref|; raises above ``limit``."""
    scale = float(np.abs(ref).max())
    if not scale > 0:
        raise RuntimeError(f"{what}: all-zero reference")
    rel = float(np.abs(rows - ref).max()) / scale
    if not rel <= limit:
        raise RuntimeError(f"{what}: largest error {rel:.3g} of the "
                           f"reference's largest value {scale:.4g}, above "
                           f"{limit}")
    return rel


def check_reference(svc, model, params, answers) -> None:
    worst = 0.0
    for (c, r), (targets, rseed, rows) in sorted(answers.items()):
        if c != 0:
            continue
        ref = reference(svc.store, model, params, targets, rseed)
        worst = max(worst, relative_error(rows, ref, REF_REL,
                                          f"{model} answer {(c, r)}"))
    log(f"  {model}: client 0 answers match the fp32 reference (max abs "
        f"error {worst:.3g} of each answer's scale; limit {REF_REL})")


def log_group_shape(svc, answers) -> None:
    """The padded shapes of a full group: every client's first request."""
    from repro.serve.batcher import pad_group, sample_group
    first = [answers[(c, 0)] for c in range(16)]
    batch, _ = sample_group(svc.store, [a[0] for a in first],
                            [a[1] for a in first], FANOUTS)
    rows = batch.num_nodes
    batch = pad_group(batch, svc.pad_to)
    log(f"  a 16-request group: {rows} sampled rows, padded to "
        f"H {batch.embeddings.shape}, blocks "
        f"{[blk.nbr.shape for blk in batch.layers]}")


def check_trace(svc, model: str, want: tuple[str, str]) -> None:
    """The last engine run must bind ``want`` and leave no operation that
    the Hetero bitstream implements on the Shell."""
    from repro.kernels.ops import BITSTREAMS
    hetero_ops = {op for mk in BITSTREAMS["hetero"] for op in mk().kernels}
    trace = list(svc.engine.trace)
    if want not in trace:
        raise RuntimeError(f"{model}: {want} missing from trace {trace}")
    on_shell = sorted({op for op, dev in trace
                       if dev == "shell" and op in hetero_ops})
    if on_shell:
        raise RuntimeError(f"{model}: {on_shell} fell back to the Shell")
    log(f"  {model}: engine trace {sorted(set(trace))}")


def one_chip(seed: int) -> None:
    t0 = time.perf_counter()
    svc, runtime, boot = start_service(*make_graph(seed))
    try:
        log(f"ingest + program hetero: {time.perf_counter() - t0:.1f} s")
        for model, clients, requests, want in (
                ("gcn", 16, 8, ("AggCombine", "vector")),
                ("gin", 4, 2, ("SpMM_Sum", "vector")),
                ("ngcf", 4, 2, ("SDDMM", "vector"))):
            t0 = time.perf_counter()
            params, _, dfg = deploy(boot, model, seed)
            answers = serve(runtime, model, dfg, clients=clients,
                            requests=requests, seed=seed)
            log(f"{model}: {len(answers)} requests served in "
                f"{time.perf_counter() - t0:.1f} s (compiles included); "
                f"engine cache {svc.engine.cache_stats()}")
            if model == "gcn":
                log_group_shape(svc, answers)
            check_trace(svc, model, want)
            check_reference(svc, model, params, answers)
        qos = boot.call("stats", timeout=600)["qos"]
        log(f"scheduler: {qos['groups']} groups, avg group size "
            f"{qos['avg_group_size']:.1f}")
    finally:
        runtime.stop()
        svc.close()


def four_chips(seed: int) -> None:
    import jax
    from repro.core.dfg import DFG, Engine

    devices = jax.devices()
    if len(devices) < 4:
        raise RuntimeError(f"--chips 4 needs 4 devices, found {len(devices)}")
    t0 = time.perf_counter()
    svc, runtime, boot = start_service(*make_graph(seed), model_parallel=4)
    try:
        mesh = svc.engine.mesh
        ids = {d.id for d in mesh.devices.flat}
        if mesh.devices.size != 4 or len(ids) != 4:
            raise RuntimeError(f"mesh spans {sorted(ids)}, not 4 devices")
        log(f"mesh {dict(zip(mesh.axis_names, mesh.devices.shape))} over "
            f"devices {sorted(ids)}; ingest {time.perf_counter() - t0:.1f} s")
        t0 = time.perf_counter()
        _, weights, dfg = deploy(boot, "gcn", seed)
        answers = serve(runtime, "gcn", dfg, clients=16, requests=8,
                        seed=seed)
        log(f"gcn (SPMD): {len(answers)} requests served in "
            f"{time.perf_counter() - t0:.1f} s; engine cache "
            f"{svc.engine.cache_stats()}")
        if ("AggCombine", "vector") not in svc.engine.trace:
            raise RuntimeError(f"no fusion in SPMD trace {svc.engine.trace}")

        # placement: the SPMD program's result lives on all four devices
        feeds = dict(weights, Batch=np.asarray(answers[(0, 0)][0]),
                     Seed=answers[(0, 0)][1])
        graph = DFG.load(dfg)
        out = svc.engine.run(graph, feeds, jit=True)["Result"]
        placed = {d.id for d in out.sharding.device_set}
        if placed != ids:
            raise RuntimeError(f"SPMD result placed on {sorted(placed)}")

        one = Engine(svc.registry)             # the one-chip engine
        worst = 0.0
        for (c, r), (targets, rseed, rows) in sorted(answers.items()):
            feeds = dict(weights, Batch=np.asarray(targets), Seed=rseed)
            ref = np.asarray(one.run(graph, feeds, jit=True)["Result"])
            ref = ref[:len(targets)]           # rows past them are padding
            worst = max(worst, relative_error(rows, ref, SPMD_REL,
                                              f"gcn answer {(c, r)}"))
        log(f"  all {len(answers)} SPMD answers match the one-chip engine "
            f"(largest error {worst:.3g} of each answer's scale; limit "
            f"{SPMD_REL})")
    finally:
        runtime.stop()
        svc.close()


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--chips", type=int, choices=(1, 4), default=1)
    args = ap.parse_args()

    import jax
    dev = jax.devices()[0]
    if dev.platform != "tpu":
        print(f"chip_smoke: no TPU found (JAX platform {dev.platform!r}); "
              "this check runs only on a TPU", file=sys.stderr)
        return 1
    sys.path.insert(0, os.path.join(os.path.dirname(os.path.abspath(
        __file__)), "src"))
    from repro.compile_cache import use_compile_cache
    log(f"device: {dev.device_kind} x{len(jax.devices())}; "
        f"compile cache {use_compile_cache()}")

    compiles: list[float] = []

    def on_event(event: str, secs: float, **_) -> None:
        if event == "/jax/core/compile/backend_compile_duration":
            compiles.append(secs)

    jax.monitoring.register_event_duration_secs_listener(on_event)
    t0 = time.perf_counter()
    (four_chips if args.chips == 4 else one_chip)(args.seed)
    log(f"total: {time.perf_counter() - t0:.1f} s, of which "
        f"{sum(compiles):.1f} s in {len(compiles)} backend compiles")
    print(json.dumps({"ok": True, "device": {
        "platform": dev.platform, "kind": dev.device_kind,
        "count": len(jax.devices())}}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
