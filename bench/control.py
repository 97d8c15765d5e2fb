"""Readings that the correctness limits are set from, on the chip.

  python3 bench/control.py --workload <cell> --seeds 11,12,13 --seconds 5

For each seed, one process sets the cell up as ``run.py`` does, serves a
short window of its traffic, and compares the same seeded sample of answers
as a run does.  It prints three worst relative errors
(``reference.rel_l2``) against the float64 reference:

  * ``program``: the served answers (the lower reading of the limit);
  * ``control``: the reference itself in float32 with its matrix products
    at ``high`` precision (bfloat16 head and tail, the step below the
    configuration's ``highest``), on the default device: the upper reading;
  * ``bf16``: the reference with one bfloat16 pass per product, for
    comparison.
"""
from __future__ import annotations

import argparse
import json
import sys
from pathlib import Path

import numpy as np

sys.path.insert(0, str(Path(__file__).resolve().parent.parent))

from bench import reference  # noqa: E402
from bench import run as R  # noqa: E402


def control_rows(served, record, matmul):
    """The reference's answer in float32 on the default device."""
    import jax.numpy as jnp
    levels, blocks = reference.sample(served.graph.neighbors,
                                      record["targets"], record["seed"],
                                      served.cell.fanouts)
    emb = jnp.asarray(served.table[np.asarray(levels[-1], np.int64)])
    blocks = [(jnp.asarray(n), jnp.asarray(m)) for n, m in blocks]
    params = [{k: jnp.asarray(v) for k, v in p.items()}
              for p in served.params]
    return np.asarray(served.cell.model.forward(emb, blocks, params, xp=jnp,
                                                matmul=matmul))


def bf16_once(xp):
    import jax.numpy as jnp

    def mm(a, b):
        return xp.matmul(a.astype(jnp.bfloat16), b.astype(jnp.bfloat16),
                         preferred_element_type=jnp.float32)
    return mm


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True)
    ap.add_argument("--seconds", type=float, default=5.0)
    args = ap.parse_args()

    bench = R.Bench(R.ROOT)
    cell = R.Cell(bench, args.workload)
    import jax
    import jax.numpy as jnp
    R.check_chip(int(cell.entry["chips"]), bench.peaks())
    from repro.compile_cache import use_compile_cache
    use_compile_cache()
    jax.config.update("jax_default_matmul_precision",
                      cell.cfg["matmul_precision"])
    want = int(cell.cfg["correct"]["sample"])
    high, once = reference.matmul_bf16x3(jnp), bf16_once(jnp)
    for seed in (int(s) for s in args.seeds.split(",")):
        served = R.Served(cell, seed)
        served.prepare()
        win = served.serve(served.requests("traffic", args.seconds),
                           args.seconds)
        served.stop()
        ok = win.ok
        pick = R.rng_for(seed, "sample").permutation(len(ok))[:want]
        sample = [ok[i] for i in sorted(pick)]
        worst = {"program": 0.0, "control": 0.0, "bf16": 0.0}
        for r in sample:
            ref = reference.answer(cell.model, served.graph.neighbors,
                                   served.table, served.params,
                                   r["targets"], r["seed"], cell.fanouts)
            for name, rows in (("program", r["rows"]),
                               ("control", control_rows(served, r, high)),
                               ("bf16", control_rows(served, r, once))):
                worst[name] = max(worst[name], reference.rel_l2(rows, ref))
        R.log("readings " + json.dumps({
            "workload": cell.name, "seed": seed, "answers": len(sample),
            "failed": len(win.failed), **worst}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
