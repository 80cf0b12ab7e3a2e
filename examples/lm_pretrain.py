"""End-to-end driver: pretrain a ~100M-param dense LM for a few hundred
steps on synthetic data, with checkpointing + fault-tolerant loop.

    PYTHONPATH=src python examples/lm_pretrain.py [--steps 300] [--tiny]
"""

import argparse
import sys

sys.path.insert(0, "src")

import dataclasses

import jax

from repro.configs import get_config
from repro.launch.cache import use_compile_cache
from repro.launch.train import train
from repro.models.common import ModelConfig

# ~100M params: 2*V*D + L*(4*D*hd*H/...): see ModelConfig.n_params
CFG_100M = ModelConfig(
    name="lm-100m", family="dense", n_layers=10, d_model=640, n_heads=10,
    n_kv_heads=5, d_ff=2560, vocab=32000, qk_norm=True, remat=False,
    dtype=jax.numpy.float32,
)


def main():
    use_compile_cache()
    ap = argparse.ArgumentParser()
    ap.add_argument("--steps", type=int, default=300)
    ap.add_argument("--tiny", action="store_true",
                    help="16M-param config for quick validation")
    ap.add_argument("--ckpt", default="/tmp/repro_lm_ckpt")
    args = ap.parse_args()

    cfg = CFG_100M if not args.tiny else dataclasses.replace(
        CFG_100M, n_layers=4, d_model=256, n_heads=4, n_kv_heads=2,
        d_ff=1024, vocab=8000, name="lm-16m")
    print(f"{cfg.name}: {cfg.n_params() / 1e6:.0f}M params")

    # route through the shared trainer by registering the config inline
    import repro.configs as configs
    mod_name = "examples_lm"
    import types
    mod = types.ModuleType(mod_name)
    mod.full_config = lambda: cfg
    mod.smoke_config = lambda: cfg
    sys.modules[f"repro.configs.{mod_name}"] = mod

    losses = train(mod_name, smoke=True, steps=args.steps, batch=8, seq=256,
                   ckpt_dir=args.ckpt, lr=6e-4, save_every=100)
    first, last = losses[0], sum(losses[-10:]) / min(10, len(losses))
    print(f"loss {first:.3f} -> {last:.3f} "
          f"({'LEARNING' if last < first - 0.1 else 'check hyperparams'})")


if __name__ == "__main__":
    main()
