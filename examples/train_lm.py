"""End-to-end training example (deliverable b).

Trains a ~100M-parameter llama-family model for a few hundred steps on
the synthetic pipeline, with async checkpointing — then kills and
resumes to demonstrate fault-tolerant restart.

On this CPU container the default invocation is scaled down; pass
--full-100m for the real 100M x 300-step run (hours on 1 CPU core,
minutes on a TPU host).

    PYTHONPATH=src python examples/train_lm.py [--full-100m]
"""

import argparse
import pathlib
import shutil
import sys

sys.path.insert(0, str(pathlib.Path(__file__).parent.parent / "src"))

from repro.compile_cache import enable_compile_cache
from repro.launch.train import main as train_main


def run(argv=None):
    ap = argparse.ArgumentParser()
    ap.add_argument("--full-100m", action="store_true")
    ap.add_argument("--ckpt", default="/tmp/repro_train_lm_ckpt")
    args = ap.parse_args(argv)
    enable_compile_cache()

    shutil.rmtree(args.ckpt, ignore_errors=True)

    if args.full_100m:
        # ~100M params: 12 x 768 llama-style, few hundred steps
        common = ["--arch", "llama3-8b", "--smoke",
                  "--d-model", "768", "--n-layers", "12",
                  "--batch", "8", "--seq", "512",
                  "--ckpt-dir", args.ckpt]
        steps = 300
    else:
        common = ["--arch", "llama3-8b", "--smoke",
                  "--batch", "4", "--seq", "64",
                  "--ckpt-dir", args.ckpt]
        steps = 60

    # phase 1: train halfway, checkpointing along the way
    half = steps // 2
    losses1 = train_main(common + ["--steps", str(half),
                                   "--ckpt-every", "10"])
    print(f"\n--- simulated failure after step {half}; restarting ---\n")
    # phase 2: rerun with the full step budget — resumes from the
    # latest checkpoint (params, optimizer, data cursor)
    losses2 = train_main(common + ["--steps", str(steps),
                                   "--ckpt-every", "10"])
    assert losses2[-1] < losses1[0], "loss should improve across restart"
    print("\nOK — training resumed from checkpoint and kept improving "
          f"({losses1[0]:.3f} -> {losses2[-1]:.3f}).")


if __name__ == "__main__":
    run()
