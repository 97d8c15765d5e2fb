"""Find a cell's knee, and how steady its latency is below it.

  python3 bench/sweep.py --workload <online cell> --seed <n> \
      --rates 10,15,20 --seconds 15 [--repeats 1]

One process sets the cell up once, as ``run.py`` does, then serves
``--repeats`` open-loop windows per rate, in the order given, each with
the cell's traffic at that rate from a stream of its own.  Per window it
prints the requests sent and answered, the rate answered within the
window, the p50, p95 and p99 latency from the due time, the mean group
size, and the median latency of the window's first and last thirds: a
backlog that grows through the window shows as a last third far above the
first.  Per rate it prints the quartile spread of p50 and p95 over the
repeats (``statistics.quantiles``, over the median).  The rate chosen for
the cell goes into its workload file by hand.
"""
from __future__ import annotations

import argparse
import json
import statistics
import sys
from pathlib import Path

import numpy as np

sys.path.insert(0, str(Path(__file__).resolve().parent.parent))

from bench import run as R  # noqa: E402


def spread(xs) -> float:
    """Distance between the quartiles over the median."""
    q = statistics.quantiles(xs, n=4)
    return (q[2] - q[0]) / statistics.median(xs)


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--rates", required=True)
    ap.add_argument("--seconds", type=float, default=15.0)
    ap.add_argument("--repeats", type=int, default=1)
    args = ap.parse_args()

    bench = R.Bench(R.ROOT)
    cell = R.Cell(bench, args.workload)
    if cell.traffic.KIND != "open":
        raise SystemExit("the sweep needs an open-loop cell")
    import jax
    R.check_chip(int(cell.entry["chips"]), bench.peaks())
    from repro.compile_cache import use_compile_cache
    use_compile_cache()
    jax.config.update("jax_default_matmul_precision",
                      cell.cfg["matmul_precision"])
    served = R.Served(cell, args.seed)
    served.prepare()
    try:
        k = 0
        for rate in (float(r) for r in args.rates.split(",")):
            p50s, p95s = [], []
            for _ in range(args.repeats):
                k += 1
                plan = cell.traffic.build(
                    dict(cell.spec, rate_rps=rate),
                    np.random.default_rng([abs(args.seed), 100 + k]),
                    args.seconds, served.graph.n, served.popularity())
                before = served.stats()["qos"]
                win = served.serve(plan, args.seconds)
                after = served.stats()["qos"]
                lat = R.latency_ms(win)
                third = np.argsort([r["due"] for r in win.records])
                n3 = max(1, len(third) // 3)
                e2e = R.end_to_end(win, 0.0)
                p50s.append(e2e["p50_ms"])
                p95s.append(e2e["p95_ms"])
                groups = after["groups"] - before["groups"]
                R.log("sweep " + json.dumps({
                    "rate": rate, "sent": len(win.records),
                    "answered": len(win.ok), "failed": len(win.failed),
                    "answered_rps": e2e["rps"], "p50_ms": e2e["p50_ms"],
                    "p95_ms": e2e["p95_ms"], "p99_ms": e2e["p99_ms"],
                    "group_size": len(win.ok) / max(groups, 1),
                    "first_third_p50_ms": float(np.median(lat[third[:n3]])),
                    "last_third_p50_ms": float(np.median(lat[third[-n3:]]))}))
            if len(p50s) > 1:
                R.log("spread " + json.dumps({
                    "rate": rate, "p50_spread": spread(p50s),
                    "p95_spread": spread(p95s)}))
    finally:
        served.stop()
    return 0


if __name__ == "__main__":
    sys.exit(main())
