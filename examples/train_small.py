"""End-to-end driver: train a ~100M-param qwen2-family model for a few
hundred steps on the synthetic bigram stream, with checkpointing.

  PYTHONPATH=src python examples/train_small.py [--steps 200]

(~100M params: d_model=768, 12 layers, ff=2560, vocab 4096 tied.)
"""
import argparse

from repro.core import compile_cache
from repro.launch import train as lt


def main():
    compile_cache.enable()
    ap = argparse.ArgumentParser()
    ap.add_argument("--steps", type=int, default=200)
    ap.add_argument("--ckpt-dir", default="/tmp/pond_train_small")
    args = ap.parse_args()
    lt.main([
        "--arch", "qwen2-1.5b", "--preset", "100m",
        "--steps", str(args.steps),
        "--global-batch", "2", "--seq-len", "128",
        "--lr", "3e-4", "--log-every", "5",
        "--ckpt-dir", args.ckpt_dir, "--ckpt-every", "50",
    ])


if __name__ == "__main__":
    main()
