"""Train the ~125M-param xlstm-125m for a few hundred steps at reduced
sequence length with checkpoint/restart (kill it mid-run and re-invoke: it
resumes from the last committed step and replays the same data stream).

  PYTHONPATH=src python examples/train_lm.py --steps 200
(full-size config; pass --smoke for a quick CPU sanity run)
"""
import argparse

from repro.compile_cache import use_compile_cache
from repro.launch.train import main as train_main

if __name__ == "__main__":
    use_compile_cache()
    ap = argparse.ArgumentParser()
    ap.add_argument("--steps", type=int, default=200)
    ap.add_argument("--smoke", action="store_true")
    a = ap.parse_args()
    argv = ["--arch", "xlstm-125m", "--steps", str(a.steps),
            "--batch", "4", "--seq", "256", "--ckpt", "/tmp/xlstm_ckpt",
            "--ckpt-every", "20"]
    if a.smoke:
        argv.append("--smoke")
    train_main(argv)
