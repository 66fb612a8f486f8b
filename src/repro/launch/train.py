"""Production training launcher.

    PYTHONPATH=src python -m repro.launch.train --arch qwen2_1_5b \
        --steps 20 --reduced --mesh 2x2

On real hardware the same entry point drives the full configs over the
production mesh (launch/mesh.py); on this CPU container ``--reduced``
runs the same code path at smoke scale.  Fault tolerance is on by
default: periodic checkpoints, automatic restore, straggler watchdog.
"""
from __future__ import annotations

import argparse
import os
import time


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default="qwen2_1_5b")
    ap.add_argument("--steps", type=int, default=20)
    ap.add_argument("--mesh", default="2x2",
                    help="'RxC' data x model, 'PxRxC' with pod axis, or "
                         "'production' / 'production-multipod'")
    ap.add_argument("--reduced", action="store_true",
                    help="reduced config (CPU-runnable)")
    ap.add_argument("--global-batch", type=int, default=8)
    ap.add_argument("--seq", type=int, default=128)
    ap.add_argument("--microbatches", type=int, default=1)
    ap.add_argument("--optimizer", default="adamw",
                    choices=["adamw", "adafactor"])
    ap.add_argument("--lr", type=float, default=3e-4)
    ap.add_argument("--ckpt-dir", default="/tmp/repro_ckpt")
    ap.add_argument("--ckpt-every", type=int, default=10)
    ap.add_argument("--device-count", type=int, default=0,
                    help="host device override (0 = real devices)")
    args = ap.parse_args()

    if args.device_count:
        os.environ["XLA_FLAGS"] = (
            f"--xla_force_host_platform_device_count={args.device_count}")

    import jax
    import jax.numpy as jnp
    from repro.configs.base import get_config, reduced_config
    from repro.launch.mesh import make_production_mesh, make_mesh
    from repro.models import transformer as T
    from repro.train.data import make_batch
    from repro.train.elastic import StragglerWatchdog, run_loop
    from repro.train.optimizer import OptConfig, make_optimizer
    from repro.train.train_step import make_train_step, shardings_for

    if args.mesh == "production":
        mesh = make_production_mesh(multi_pod=False)
    elif args.mesh == "production-multipod":
        mesh = make_production_mesh(multi_pod=True)
    else:
        dims = tuple(int(x) for x in args.mesh.split("x"))
        axes = ("pod", "data", "model")[-len(dims):]
        mesh = make_mesh(dims, axes)

    cfg = get_config(args.arch)
    if args.reduced:
        cfg = reduced_config(cfg)
    print(f"arch={cfg.name} mesh={dict(mesh.shape)} steps={args.steps}")

    opt = make_optimizer(OptConfig(name=args.optimizer, lr=args.lr))
    p_sh, o_sh, b_sh = shardings_for(cfg, mesh, opt)

    params = T.model_init(cfg, jax.random.PRNGKey(0))
    params = jax.device_put(params, p_sh)
    opt_state = jax.device_put(opt.init(params), o_sh)
    n_params = sum(p.size for p in jax.tree_util.tree_leaves(params))
    print(f"params: {n_params/1e6:.1f}M")

    step_fn = jax.jit(
        make_train_step(cfg, mesh, opt, n_microbatches=args.microbatches),
        in_shardings=(p_sh, o_sh, b_sh), donate_argnums=(0, 1))

    def mb(step):
        b = make_batch(step, global_batch=args.global_batch,
                       seq_len=args.seq, vocab=cfg.vocab_size,
                       input_mode=cfg.input_mode, d_model=cfg.d_model)
        return jax.device_put({k: jnp.asarray(v) for k, v in b.items()}, b_sh)

    watchdog = StragglerWatchdog()
    with jax.set_mesh(mesh):
        result = run_loop(
            train_step=step_fn, make_batch=mb, params=params,
            opt_state=opt_state, n_steps=args.steps,
            ckpt_dir=args.ckpt_dir, ckpt_every=args.ckpt_every,
            watchdog=watchdog)
    hist = result["history"]
    print(f"done: {len(hist)} steps, restarts={result['restarts']}, "
          f"stragglers={result['stragglers']}")
    if hist:
        print(f"loss {hist[0]['loss']:.4f} -> {hist[-1]['loss']:.4f}")


if __name__ == "__main__":
    main()
