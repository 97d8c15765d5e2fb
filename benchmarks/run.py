"""Benchmark harness — one module per paper table/figure.

  PYTHONPATH=src:. python -m benchmarks.run [--only fig3,fig14,...] [--smoke]

Prints ``name,us_per_call,derived`` CSV (scaffold contract).  ``--smoke``
runs a CI-sized subset (fig19 batch-prep + fig21 fast-path + fig22 serving
+ fig23 sharding + fig24 replication + fig25 multi-host + fig27 ingest on
the small workloads) so sampler/engine/scale-out perf regressions surface
at PR time.  The
roofline table (LM archs) reads the dry-run artifacts; run
``python -m repro.launch.dryrun --all --both-meshes`` first for §Roofline.
"""
from __future__ import annotations

import argparse
import os
import sys
import time
import traceback


def _parse_line(line: str, suite: str) -> dict:
    """``name,us_per_call,derived`` CSV line -> JSON-able record."""
    name, us, derived = (line.split(",", 2) + ["", ""])[:3]
    try:
        us_f = float(us)
    except ValueError:
        us_f = None
    return {"suite": suite, "name": name, "us_per_call": us_f,
            "derived": derived}


def main(argv=None) -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--only", default=None)
    ap.add_argument("--smoke", action="store_true",
                    help="CI subset: fig19 + fig21 on the small workload")
    ap.add_argument("--json", default=None, metavar="PATH",
                    help="also write results as a JSON list (CI uploads "
                         "benchmarks/*.json as workflow artifacts)")
    ap.add_argument("--history", action="store_true",
                    help="append this run's records (timestamped) to "
                         "benchmarks/BENCH_history.json so perf drift is "
                         "trackable across CI runs")
    args = ap.parse_args(argv)

    # fig28's mesh equivalence needs a multi-device host pool; the flag
    # only takes effect if set before jax initializes, i.e. before the
    # fig-module imports below pull in jax via benchmarks.common
    if "jax" not in sys.modules:
        _flags = os.environ.get("XLA_FLAGS", "")
        if "xla_force_host_platform_device_count" not in _flags:
            os.environ["XLA_FLAGS"] = (
                _flags + " --xla_force_host_platform_device_count=8").strip()

    from repro.compile_cache import use_compile_cache
    use_compile_cache()
    from . import (fig3_breakdown, fig14_end2end, fig15_energy,
                   fig16_pure_inference, fig17_opbreakdown, fig18_bulk,
                   fig19_batchprep, fig20_mutable, fig21_fastpath,
                   fig22_serving, fig23_sharded, fig24_replicated,
                   fig25_multihost, fig26_autonomic, fig27_ingest,
                   fig28_spmd, fig29_reshard, table5_datasets)
    suites = {
        "table5": table5_datasets.run,
        "fig3": fig3_breakdown.run,
        "fig14": fig14_end2end.run,
        "fig15": fig15_energy.run,
        "fig16": fig16_pure_inference.run,
        "fig17": fig17_opbreakdown.run,
        "fig18": fig18_bulk.run,
        "fig19": fig19_batchprep.run,
        "fig20": fig20_mutable.run,
        "fig21": fig21_fastpath.run,
        "fig22": fig22_serving.run,
        "fig23": fig23_sharded.run,
        "fig24": fig24_replicated.run,
        "fig25": fig25_multihost.run,
        "fig26": fig26_autonomic.run,
        "fig27": fig27_ingest.run,
        "fig28": fig28_spmd.run,
        "fig29": fig29_reshard.run,
    }
    if args.smoke:
        suites = {
            "fig19": lambda: fig19_batchprep.run(workloads=("chmleon",)),
            "fig21": lambda: fig21_fastpath.run(smoke=True),
            "fig22": lambda: fig22_serving.run(smoke=True),
            "fig23": lambda: fig23_sharded.run(smoke=True),
            "fig24": lambda: fig24_replicated.run(smoke=True),
            "fig25": lambda: fig25_multihost.run(smoke=True),
            "fig26": lambda: fig26_autonomic.run(smoke=True),
            "fig27": lambda: fig27_ingest.run(smoke=True),
            "fig28": lambda: fig28_spmd.run(smoke=True),
            "fig29": lambda: fig29_reshard.run(smoke=True),
        }
    only = set(args.only.split(",")) if args.only else None
    print("name,us_per_call,derived")
    failures = 0
    records: list[dict] = []
    for name, fn in suites.items():
        if only and name not in only:
            continue
        t0 = time.perf_counter()
        try:
            for line in fn():
                print(line)
                records.append(_parse_line(line, name))
            wall = f"{name}.suite_wall,{(time.perf_counter()-t0)*1e6:.0f},ok"
        except Exception:  # noqa: BLE001
            traceback.print_exc()
            failures += 1
            wall = f"{name}.suite_wall,0,FAILED"
        print(wall)
        records.append(_parse_line(wall, name))
    if args.json:
        import json
        with open(args.json, "w") as fh:
            json.dump(records, fh, indent=1)
        print(f"# wrote {len(records)} records to {args.json}",
              file=sys.stderr)
    if args.history:
        import json
        path = os.path.join(os.path.dirname(__file__), "BENCH_history.json")
        try:
            with open(path) as fh:
                history = json.load(fh)
            assert isinstance(history, list)
        except (FileNotFoundError, ValueError, AssertionError):
            history = []
        history.append({
            "ts": time.strftime("%Y-%m-%dT%H:%M:%SZ", time.gmtime()),
            "smoke": bool(args.smoke),
            "only": args.only,
            "failures": failures,
            "records": records,
        })
        with open(path, "w") as fh:
            json.dump(history, fh, indent=1)
        print(f"# appended run ({len(records)} records) to {path} "
              f"({len(history)} runs)", file=sys.stderr)
    # roofline summary (if dry-run artifacts exist)
    try:
        from .roofline import load_records, table
        recs = load_records(os.path.join(os.path.dirname(__file__), "..",
                                         "results", "dryrun"))
        if recs:
            rows = table(recs, mesh_filter="16x16")
            for r in rows:
                print(f"roofline.{r['arch']}.{r['shape']},"
                      f"{r['bound_s']*1e6:.0f},"
                      f"bound={r['bound']};frac={r['roofline_fraction']:.3f};"
                      f"useful={r['useful_flops_ratio']:.2f}")
    except Exception:  # noqa: BLE001
        traceback.print_exc()
    if failures:
        sys.exit(1)


if __name__ == "__main__":
    main()
