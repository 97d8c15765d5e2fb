import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=512"
# The two lines above MUST run before any other import (jax locks the device
# count at first init).  Everything below is ordinary.
"""Multi-pod dry-run: lower + compile every (architecture x input-shape) on
the production mesh; record memory analysis, cost analysis, and the
collective schedule for the roofline.

  PYTHONPATH=src python -m repro.launch.dryrun --arch llama3.2-3b --shape train_4k
  PYTHONPATH=src python -m repro.launch.dryrun --all [--multi-pod] [--dev]

``--dev`` shrinks meshes (2x4 / 2x2x4) and shapes for fast iteration on this
CPU container; the recorded artifacts for EXPERIMENTS.md always come from
the full 512-device run.
"""
import argparse
import dataclasses
import json
import sys
import time
import traceback

import numpy as np
import jax
import jax.numpy as jnp
from jax.sharding import NamedSharding, PartitionSpec as P

from ..configs import ARCHS, SMOKES, SHAPES, shapes_for
from ..configs.base import ShapeConfig
from ..models import build, layers as L
from ..train import optimizer as O
from ..train.trainer import make_train_step
from .mesh import make_mesh, make_production_mesh, dp_axes_of

RESULTS_DIR = os.path.join(os.path.dirname(__file__), "..", "..", "..",
                           "results", "dryrun")


def _ns(mesh, tree):
    return jax.tree.map(lambda s: NamedSharding(mesh, s), tree)


def _is_long_mode(shape: ShapeConfig) -> bool:
    return shape.kind == "decode" and shape.global_batch == 1


def lower_cell(cfg, shape: ShapeConfig, mesh, *, donate=True):
    """Returns (lowered, aux_info). Must be called inside `with mesh`."""
    dp = dp_axes_of(mesh)
    long_mode = _is_long_mode(shape)
    tp = int(mesh.shape["model"])
    with L.use_mesh(mesh, dp_axes=() if long_mode else dp):
        api = build(cfg, tp=tp)
        abs_params = api.abstract_params(
            dtype=None if shape.kind == "train" else "bfloat16")
        p_sh = _ns(mesh, api.param_pspecs())
        in_specs = api.input_specs(shape)
        in_sh = _ns(mesh, api.input_pspecs(shape))
        vocab_ok = cfg.vocab_size % tp == 0
        logits_spec = L.resolve_pspec((() if long_mode else L.DP, None,
                                       "model" if vocab_ok else None))

        if shape.kind == "train":
            opt_cfg = O.AdamWConfig()
            step = make_train_step(api, opt_cfg)
            abs_opt = O.abstract_state(abs_params)
            o_sh = _ns(mesh, O.opt_pspecs(
                api.param_defs(), dp_axes=dp,
                dp_size=int(np.prod([mesh.shape[a] for a in dp]))))
            jitted = jax.jit(
                step,
                in_shardings=(p_sh, o_sh, in_sh),
                out_shardings=(p_sh, o_sh, None),
                donate_argnums=(0, 1) if donate else ())
            lowered = jitted.lower(abs_params, abs_opt, in_specs)
        elif shape.kind == "prefill":
            cache_seq = shape.seq_len
            abs_cache = api.abstract_cache(shape.global_batch, cache_seq)
            c_sh = _ns(mesh, api.cache_pspecs(shape.global_batch, cache_seq))

            def prefill_step(params, batch, caches):
                return api.prefill(params, batch, caches)

            jitted = jax.jit(
                prefill_step,
                in_shardings=(p_sh, in_sh, c_sh),
                donate_argnums=(2,) if donate else ())
            lowered = jitted.lower(abs_params, in_specs, abs_cache)
        else:  # decode
            cache_seq = shape.seq_len
            abs_cache = api.abstract_cache(shape.global_batch, cache_seq,
                                           long_mode=long_mode)
            c_pspecs = api.cache_pspecs(shape.global_batch, cache_seq,
                                        long_mode=long_mode)
            c_sh = _ns(mesh, c_pspecs)

            def serve_step(params, batch, caches):
                return api.decode(params, batch, caches)

            jitted = jax.jit(
                serve_step,
                in_shardings=(p_sh, in_sh, c_sh),
                out_shardings=(NamedSharding(mesh, logits_spec), c_sh),
                donate_argnums=(2,) if donate else ())
            lowered = jitted.lower(abs_params, in_specs, abs_cache)
    total, active = cfg.param_count()
    return lowered, {"params_total": total, "params_active": active}


def analyze(lowered, compiled, *, chips: int, shape: ShapeConfig, aux) -> dict:
    from benchmarks.hlo_analysis import expanded_analysis
    out = dict(aux)
    try:
        mem = compiled.memory_analysis()
        out["memory"] = {
            k: int(getattr(mem, k)) for k in
            ("argument_size_in_bytes", "output_size_in_bytes",
             "temp_size_in_bytes", "generated_code_size_in_bytes",
             "alias_size_in_bytes")
            if hasattr(mem, k)}
    except Exception as e:  # noqa: BLE001
        out["memory"] = {"error": str(e)}
    try:
        cost = compiled.cost_analysis()
        out["cost_raw"] = {k: float(v) for k, v in cost.items()
                           if isinstance(v, (int, float)) and
                           k in ("flops", "bytes accessed",
                                 "transcendentals", "optimal_seconds")}
    except Exception as e:  # noqa: BLE001
        out["cost_raw"] = {"error": str(e)}
    # loop-expanded per-device analysis (cost_analysis does not expand
    # while-loop trip counts and our stacks are scanned — see
    # benchmarks/hlo_analysis.py)
    txt = compiled.as_text()
    ea = expanded_analysis(txt)
    out["hlo_flops"] = ea["flops"]              # per device, loop-expanded
    out["hlo_bytes"] = ea["bytes"]
    out["unknown_loops"] = ea["unknown_loops"]
    out["collectives"] = ea["collectives"]
    out["hlo_lines"] = txt.count("\n")

    # MODEL_FLOPS: 6*N_active*D train; 2*N_active*D forward-only
    n_act = aux["params_active"]
    if shape.kind == "train":
        tokens = shape.global_batch * shape.seq_len
        out["model_flops"] = 6.0 * n_act * tokens
    elif shape.kind == "prefill":
        tokens = shape.global_batch * shape.seq_len
        out["model_flops"] = 2.0 * n_act * tokens
    else:
        out["model_flops"] = 2.0 * n_act * shape.global_batch
    out["chips"] = chips
    return out


def run_cell(arch: str, shape_name: str, *, multi_pod: bool, dev: bool,
             smoke: bool = False, out_dir: str | None = None) -> dict:
    cfg = (SMOKES if smoke else ARCHS)[arch]
    shape = SHAPES[shape_name]
    if dev:
        mesh = make_mesh((2, 2, 4) if multi_pod else (2, 4),
                         ("pod", "data", "model") if multi_pod
                         else ("data", "model"))
        shape = dataclasses.replace(
            shape, global_batch=max(mesh.shape.get("pod", 1)
                                    * mesh.shape["data"],
                                    shape.global_batch // 32),
            seq_len=min(shape.seq_len, 512))
    else:
        mesh = make_production_mesh(multi_pod=multi_pod)
    chips = int(np.prod(list(mesh.shape.values())))
    t0 = time.perf_counter()
    with mesh:
        lowered, aux = lower_cell(cfg, shape, mesh)
        t_lower = time.perf_counter() - t0
        compiled = lowered.compile()
        t_compile = time.perf_counter() - t0 - t_lower
        rec = analyze(lowered, compiled, chips=chips, shape=shape, aux=aux)
    rec.update(arch=arch, shape=shape_name, multi_pod=multi_pod,
               mesh=dict(mesh.shape), lower_s=round(t_lower, 2),
               compile_s=round(t_compile, 2), dev=dev,
               seq_len=shape.seq_len, global_batch=shape.global_batch,
               kind=shape.kind)
    print(json.dumps({k: rec[k] for k in
                      ("arch", "shape", "multi_pod", "chips", "hlo_flops",
                       "hlo_bytes", "model_flops", "compile_s")}, indent=None))
    mem = rec.get("memory", {})
    print(f"  memory_analysis: {mem}")
    cb = rec["collectives"]
    print(f"  collectives: total={cb['total_bytes']/1e9:.3f} GB "
          f"{cb['count_by_kind']}")
    if out_dir:
        os.makedirs(out_dir, exist_ok=True)
        base = f"{arch}__{shape_name}__{'pod2' if multi_pod else 'pod1'}"
        with open(os.path.join(out_dir, base + ".json"), "w") as f:
            json.dump(rec, f, indent=1)
        import gzip
        with gzip.open(os.path.join(out_dir, base + ".hlo.gz"), "wt") as f:
            f.write(compiled.as_text())
    return rec


def main(argv=None):
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default=None)
    ap.add_argument("--shape", default=None)
    ap.add_argument("--all", action="store_true")
    ap.add_argument("--multi-pod", action="store_true")
    ap.add_argument("--both-meshes", action="store_true")
    ap.add_argument("--dev", action="store_true")
    ap.add_argument("--smoke", action="store_true")
    ap.add_argument("--out", default=os.path.abspath(RESULTS_DIR))
    args = ap.parse_args(argv)

    cells = []
    if args.all:
        for a in ARCHS:
            for s in shapes_for(a):
                cells.append((a, s.name))
    else:
        assert args.arch and args.shape, "--arch/--shape or --all"
        cells = [(args.arch, args.shape)]

    meshes = [args.multi_pod]
    if args.both_meshes:
        meshes = [False, True]

    failures = []
    for arch, shape in cells:
        for mp in meshes:
            try:
                run_cell(arch, shape, multi_pod=mp, dev=args.dev,
                         smoke=args.smoke, out_dir=args.out)
            except Exception as e:  # noqa: BLE001
                traceback.print_exc()
                failures.append((arch, shape, mp, str(e)))
    if failures:
        print(f"\nFAILED {len(failures)} cells:")
        for f in failures:
            print("  ", f)
        sys.exit(1)
    print(f"\nALL {len(cells) * len(meshes)} dry-run cells compiled OK")


if __name__ == "__main__":
    main()
