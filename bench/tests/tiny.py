"""A throwaway benchmark root with a tiny cell, for the CPU tests.

It copies ``bench/`` and adds a configuration, two cells and, if asked, a
metric reader as files, and a ``BENCHMARK.json`` that names them: what a
later change that adds a cell would add.
"""
from __future__ import annotations

import argparse
import json
import shutil
from pathlib import Path

REPO = Path(__file__).resolve().parents[2]

TINY = {
    "name": "tiny", "source": "tests", "model": "gcn",
    "widths": [24, 16, 8], "fanouts": [3, 2],
    "graph": {"vertices": 300, "edges": 1200, "features": 24,
              "generator": "chung_lu", "gamma": 2.5, "seed": 1},
    "dtype": "float32", "matmul_precision": "highest",
    "service": {"h_threshold": 8, "pad_to": 32, "cache_pages": 64,
                "max_group": 2, "n_queues": 2, "max_pending": 64,
                "bitstream": "hetero", "jit_cache_size": 64},
    "correct": {"rel_l2_limit": 1.5e-06, "sample": 6},
    "reduced": [],
}


def at_config_widths(name: str, vertices: int = 1500,
                     edges: int = 12000) -> dict:
    """A benchmark configuration's model, widths, fanouts, precision and
    limit over a small graph and a small service, under the name ``tiny``."""
    cfg = json.loads((REPO / "bench" / "configs" / f"{name}.json")
                     .read_text())
    cfg["name"] = "tiny"
    cfg["graph"] = dict(cfg["graph"], vertices=vertices, edges=edges)
    cfg["service"] = dict(TINY["service"])
    cfg["correct"] = dict(cfg["correct"], sample=TINY["correct"]["sample"])
    return cfg


def make_root(tmp: Path, *, extra_metric: str | None = None,
              cfg: dict | None = None) -> Path:
    """``cfg`` replaces the ``tiny`` configuration."""
    root = Path(tmp) / "root"
    shutil.copytree(REPO / "bench", root / "bench",
                    ignore=shutil.ignore_patterns("tests", "out",
                                                  "__pycache__"))
    (root / "bench" / "configs" / "tiny.json").write_text(
        json.dumps(cfg or TINY))
    (root / "bench" / "workloads" / "tiny.online.json").write_text(
        json.dumps({"config": "tiny", "traffic": "open_poisson",
                    "rate_rps": 6.0, "targets_per_request": 3,
                    "targets": {"law": "zipf", "s": 0.99}, "why": "tiny"}))
    (root / "bench" / "workloads" / "tiny.closed.json").write_text(
        json.dumps({"config": "tiny", "traffic": "closed", "callers": 4,
                    "targets_per_request": 3,
                    "targets": {"law": "permutation"}, "why": "tiny"}))
    spec = json.loads((REPO / "BENCHMARK.json").read_text())
    spec["configs"] = [{"name": "tiny", "source": "tests",
                        "file": "bench/configs/tiny.json", "reduced": [],
                        "why": "tiny"}]
    spec["workloads"] = [
        {"name": f"tiny.{t}", "config": "tiny", "traffic": t, "chips": 1,
         "why": "tiny"} for t in ("online", "closed")]
    for m in spec["end_to_end"] + spec["per_layer"]:
        if "workloads" in m:
            m["workloads"] = sorted({"tiny." + w.split(".", 1)[1]
                                     for w in m["workloads"]})
    if extra_metric:
        (root / "bench" / "metrics" / f"{extra_metric}.py").write_text(
            "def read(ctx):\n    return ctx['completed'] + 0.5\n")
        spec["per_layer"].append(
            {"name": extra_metric, "unit": "requests", "better": "higher",
             "source": "program_counter", "layer": "scheduler and batcher",
             "moves": "p50_ms", "workloads": ["tiny.online"]})
    (root / "BENCHMARK.json").write_text(json.dumps(spec))
    return root


def args(workload: str, *, seed: int = 2**31 + 7, seconds: float = 1.5,
         trace: int = 0) -> argparse.Namespace:
    return argparse.Namespace(workload=workload, seed=seed,
                              seconds=seconds, trace=trace)
