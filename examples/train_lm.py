"""Train a ~100M-parameter LM from the zoo for a few hundred steps on the
synthetic corpus, with checkpoints — exercises the full training substrate
(model zoo, data pipeline, AdamW, checkpoint/resume).

Run:  PYTHONPATH=src python examples/train_lm.py [--steps 200]
"""
import argparse

from repro.configs import ModelConfig
from repro.configs import registry as reg
from repro.launch import train as train_mod


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--steps", type=int, default=200)
    ap.add_argument("--arch", default="granite-moe-1b-a400m")
    ap.add_argument("--ckpt", default="results/ckpt_lm")
    args = ap.parse_args()

    # ~100M-class reduced config: granite-moe reduced is small; train longer
    # sequences and a wider batch to make the run meaningful on CPU.
    result = train_mod.main([
        "--arch", args.arch, "--steps", str(args.steps),
        "--global-batch", "8", "--seq-len", "128",
        "--ckpt-dir", args.ckpt, "--ckpt-every", "50",
        "--lr", "1e-3", "--log-every", "25",
    ])
    assert result["last_loss"] < result["first_loss"], "training must learn"
    print(f"loss {result['first_loss']:.3f} -> {result['last_loss']:.3f} "
          f"({args.steps} steps); checkpoints in {args.ckpt}")


if __name__ == "__main__":
    from repro.jax_cache import use_compile_cache
    use_compile_cache()
    main()
