"""End-to-end serving driver (the paper's kind: serve a small model with
batched requests).

Real (reduced) models run behind the scheduler: the GDM DiT denoiser and an
LM decode service are chained block-by-block across heterogeneous nodes,
with greedy-MAC admission, adaptive chain length (early exit at the quality
threshold), latent-shipping costs, and the full objective bookkeeping (2).
Compares adaptive vs fixed chain length end to end.

Run:  PYTHONPATH=src python examples/serve_edge.py
"""
import numpy as np

from repro.launch import serve as serve_mod


def main():
    print("=== adaptive chain length (LEARN-GDM serving mode) ===")
    adaptive = serve_mod.main(["--frames", "24", "--requests", "12",
                               "--nodes", "4", "--blocks", "4", "--seed", "0"])

    print("\n=== fixed chain length (FP serving mode) ===")
    fixed = serve_mod.main(["--frames", "24", "--requests", "12",
                            "--nodes", "4", "--blocks", "4", "--seed", "0",
                            "--no-early-exit"])

    print("\nsummary:")
    print(f"  adaptive: quality={adaptive['mean_quality']:.3f} "
          f"latency={adaptive['mean_latency_frames']:.1f}f "
          f"objective={adaptive['objective']:.2f}")
    print(f"  fixed:    quality={fixed['mean_quality']:.3f} "
          f"latency={fixed['mean_latency_frames']:.1f}f "
          f"objective={fixed['objective']:.2f}")
    print("(adaptive should trade a little quality for much lower latency "
          "and a better objective under load — the paper's core claim)")


if __name__ == "__main__":
    from repro.jax_cache import use_compile_cache
    use_compile_cache()
    main()
