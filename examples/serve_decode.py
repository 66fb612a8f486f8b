"""Serving example: prefill a batch of prompts, then decode tokens
greedily with the KV/state caches — exercises the same decode_step the
decode_32k / long_500k dry-run shapes lower.

Works for every family: attention KV caches, MLA latent caches, Mamba
conv+ssm states, RWKV wkv states (try --arch rwkv6_1_6b).

    PYTHONPATH=src python examples/serve_decode.py --arch qwen2_1_5b
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
from repro.serve import engine
from repro.serve.prefill import prefill_step


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default="qwen2_1_5b")
    ap.add_argument("--batch", type=int, default=4)
    ap.add_argument("--prompt-len", type=int, default=32)
    ap.add_argument("--gen", type=int, default=32)
    args = ap.parse_args()

    cfg = reduced_config(get_config(args.arch))
    mesh = make_mesh((2, 2), ("data", "model"))
    params = T.model_init(cfg, jax.random.PRNGKey(0))
    rng = np.random.RandomState(0)
    max_len = args.prompt_len + args.gen + 8

    if cfg.input_mode == "embeddings":
        prompts = jnp.asarray(rng.randn(
            args.batch, args.prompt_len, cfg.d_model).astype(np.float32))
    else:
        prompts = jnp.asarray(rng.randint(
            0, cfg.vocab_size, (args.batch, args.prompt_len)).astype(np.int32))

    with jax.set_mesh(mesh):
        # ---- prefill ---------------------------------------------------
        t0 = time.perf_counter()
        first_tok, cache, cur = jax.jit(
            lambda p, x: prefill_step(p, x, cfg, mesh))(params, prompts)
        jax.block_until_ready(first_tok)
        t_prefill = time.perf_counter() - t0
        # embed prefill caches into the decode cache of max_len
        target = T.cache_shapes(cfg, args.batch, max_len)
        cache = jax.tree_util.tree_map(
            lambda x, t: jnp.pad(jnp.asarray(x),
                                 [(0, ts - xs) for xs, ts in
                                  zip(x.shape, t.shape)]).astype(t.dtype),
            cache, target)
        state = {"cache": cache, "cur_len": cur}

        # ---- decode loop -------------------------------------------------
        decode = jax.jit(lambda p, s, t: engine.decode_step(p, s, t, cfg, mesh),
                         donate_argnums=(1,))
        tok = first_tok
        generated = [np.asarray(tok)]
        t0 = time.perf_counter()
        for _ in range(args.gen - 1):
            if cfg.input_mode == "embeddings":
                # stub frontend: feed the embedding of the sampled token id
                feed = jnp.take(params["embed"], tok[:, 0], axis=0)[:, None]
                tok, state = decode(params, state, feed.astype(jnp.float32))
            else:
                tok, state = decode(params, state, tok)
            generated.append(np.asarray(tok))
        jax.block_until_ready(tok)
        t_decode = time.perf_counter() - t0

    toks = np.concatenate(generated, axis=1)
    print(f"arch={cfg.name}  prefill({args.prompt_len} toks): "
          f"{t_prefill*1e3:.1f} ms   decode: "
          f"{t_decode/max(args.gen-1,1)*1e3:.2f} ms/token")
    print(f"generated token ids (first sequence): {toks[0][:16]} ...")
    assert toks.shape == (args.batch, args.gen)
    print("OK")


if __name__ == "__main__":
    main()
