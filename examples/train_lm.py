"""End-to-end LM training driver (deliverable (b)): synthetic data ->
sharded train loop -> checkpoints -> recovery, on any of the 10 archs
at a reduced width.

Default runs a ~25M-param qwen2-style model for 30 steps on CPU in a
couple of minutes; ``--preset 100m --steps 300`` is the full
"train ~100M model for a few hundred steps" configuration (same code
path, bigger dims — budget ~hours on 1 CPU core, minutes on a real
accelerator).

    PYTHONPATH=src python examples/train_lm.py --steps 30
    PYTHONPATH=src python examples/train_lm.py --preset 100m --steps 300
"""
import os
os.environ.setdefault("XLA_FLAGS", "--xla_force_host_platform_device_count=4")

import argparse
import time

import jax
import jax.numpy as jnp
import numpy as np

from repro.configs.base import get_config, reduced_config
from repro.launch.mesh import make_mesh
from repro.models import transformer as T
from repro.train.data import make_batch
from repro.train.elastic import StragglerWatchdog, run_loop
from repro.train.checkpoint import CheckpointManager
from repro.train.optimizer import OptConfig, make_optimizer
from repro.train.train_step import make_train_step

PRESETS = {
    # ~25M params: quick CPU sanity run
    "25m": dict(d_model=256, num_layers=8, num_heads=8, num_kv_heads=2,
                head_dim=32, d_ff=1024, vocab_size=4096, dtype="float32"),
    # ~100M params: the deliverable configuration
    "100m": dict(d_model=640, num_layers=12, num_heads=10, num_kv_heads=2,
                 head_dim=64, d_ff=2560, vocab_size=32768, dtype="float32"),
}


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default="qwen2_1_5b")
    ap.add_argument("--preset", default="25m", choices=list(PRESETS))
    ap.add_argument("--steps", type=int, default=30)
    ap.add_argument("--batch", type=int, default=8)
    ap.add_argument("--seq", type=int, default=128)
    ap.add_argument("--lr", type=float, default=1e-3)
    ap.add_argument("--ckpt-dir", default="/tmp/repro_train_lm")
    ap.add_argument("--ckpt-every", type=int, default=50)
    args = ap.parse_args()

    cfg = reduced_config(get_config(args.arch), **PRESETS[args.preset])
    mesh = make_mesh((2, 2), ("data", "model"))
    print(f"arch={cfg.name} (reduced {args.preset}), mesh {mesh.devices.shape}")

    params = T.model_init(cfg, jax.random.PRNGKey(0))
    n_params = sum(p.size for p in jax.tree_util.tree_leaves(params))
    print(f"params: {n_params/1e6:.1f}M")

    opt = make_optimizer(OptConfig(lr=args.lr))
    opt_state = opt.init(params)
    step_fn = jax.jit(make_train_step(cfg, mesh, opt), donate_argnums=(0, 1))

    def mb(step):
        b = make_batch(step, global_batch=args.batch, seq_len=args.seq,
                       vocab=cfg.vocab_size, input_mode=cfg.input_mode,
                       d_model=cfg.d_model)
        return {k: jnp.asarray(v) for k, v in b.items()}

    watchdog = StragglerWatchdog()
    t0 = time.time()
    with jax.set_mesh(mesh):
        result = run_loop(
            train_step=step_fn, make_batch=mb, params=params,
            opt_state=opt_state, n_steps=args.steps,
            ckpt_dir=args.ckpt_dir, ckpt_every=args.ckpt_every,
            watchdog=watchdog)
    hist = result["history"]
    dt = time.time() - t0
    print(f"\n{len(hist)} steps in {dt:.1f}s "
          f"({dt/max(len(hist),1):.2f} s/step), restarts={result['restarts']}")
    for h in hist[:3] + hist[-3:]:
        print(f"  step {h['step']:4d}  loss {h['loss']:.4f}  {h['dt']*1e3:.0f} ms")
    first, last = hist[0]["loss"], hist[-1]["loss"]
    print(f"loss: {first:.4f} -> {last:.4f} "
          f"({'OK: decreasing' if last < first else 'WARNING: not decreasing'})")


if __name__ == "__main__":
    main()
