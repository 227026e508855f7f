"""End-to-end LM training driver (deliverable b): train a ~100M-param
llama3-family model for a few hundred steps on CPU with checkpointing.

  PYTHONPATH=src python examples/train_lm.py [--steps 300]
"""
import argparse
import dataclasses
import sys
import tempfile

sys.path.insert(0, "src")

from repro.compile_cache import enable_compile_cache  # noqa: E402
from repro.configs import get_config          # noqa: E402
from repro.launch.train import main as train  # noqa: E402
import repro.configs.llama3_8b as l3          # noqa: E402


def make_100m():
    """~100M-param llama3-family config (12L, d=768)."""
    return dataclasses.replace(
        l3.CONFIG, name="llama3-100m", num_layers=12, d_model=768,
        num_heads=12, num_kv_heads=4, d_ff=2048, vocab_size=32000,
        head_dim=64)


if __name__ == "__main__":
    ap = argparse.ArgumentParser()
    ap.add_argument("--steps", type=int, default=300)
    ap.add_argument("--batch", type=int, default=4)
    ap.add_argument("--seq", type=int, default=256)
    args = ap.parse_args()
    enable_compile_cache()

    # register the 100M config in place of the smoke config
    import repro.launch.train as TR
    cfg = make_100m()
    TR.get_smoke_config = lambda arch: cfg

    with tempfile.TemporaryDirectory() as d:
        loss = train(["--arch", "llama3-8b", "--smoke",
                      "--steps", str(args.steps),
                      "--batch", str(args.batch),
                      "--seq", str(args.seq),
                      "--schedule", "wsd",
                      "--ckpt-dir", d, "--ckpt-every", "100",
                      "--log-every", "20"])
    print(f"final loss: {loss:.4f}")
