"""End-to-end training driver: train a small LM for a few hundred steps with
checkpointing, resumable data, and straggler watchdog.

    PYTHONPATH=src python examples/train_lm.py --steps 300

``--size tiny`` (default) trains a reduced minitron config in CPU-friendly
time; ``--size 100m`` selects xlstm-350m at full width (for real hardware).
The driver is `repro.launch.train` — the same code path the production
launcher uses, including auto-resume from the newest valid checkpoint.
"""
import argparse
import os
import sys
import tempfile

sys.path.insert(0, "src")

from repro.launch.train import main as train_main
from repro.launch.platform import use_compile_cache


def main() -> None:
    use_compile_cache(
        os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
    ap = argparse.ArgumentParser()
    ap.add_argument("--steps", type=int, default=200)
    ap.add_argument("--size", choices=["tiny", "100m"], default="tiny")
    ap.add_argument("--ckpt-dir", default=None)
    args = ap.parse_args()
    ckpt = args.ckpt_dir or tempfile.mkdtemp(prefix="repro_ckpt_")
    argv = ["--arch", "xlstm-350m" if args.size == "100m" else "minitron-8b",
            "--steps", str(args.steps), "--batch", "4", "--seq", "64",
            "--ckpt-dir", ckpt, "--ckpt-every", "50", "--log-every", "20"]
    if args.size == "tiny":
        argv.append("--smoke")
    out = train_main(argv)
    losses = out["losses"]
    print(f"\ntrained {len(losses)} steps: loss {losses[0]:.3f} -> "
          f"{losses[-1]:.3f}; checkpoints in {ckpt}")
    assert losses[-1] < losses[0], "loss must decrease"


if __name__ == "__main__":
    main()
