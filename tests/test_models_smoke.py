"""Per-architecture smoke tests: REDUCED config of the same family, one
forward/train step on CPU, asserting output shapes + no NaNs (the FULL
configs are exercised only via the dry-run)."""
import numpy as np
import pytest

import jax
import jax.numpy as jnp

from repro.configs.base import ARCHS, get_config, reduced_config
from repro.launch.mesh import make_mesh
from repro.models import transformer as T
from repro.serve import engine
from repro.serve.prefill import prefill_step
from repro.train.optimizer import OptConfig, make_optimizer
from repro.train.train_step import make_train_step


@pytest.fixture(scope="module")
def mesh():
    # single real device: 1x1x1 production-shaped mesh
    return make_mesh((1, 1, 1), ("pod", "data", "model"))


def _batch(cfg, b=2, s=16, seed=0):
    rng = np.random.RandomState(seed)
    labels = rng.randint(0, cfg.vocab_size, (b, s)).astype(np.int32)
    if cfg.input_mode == "embeddings":
        inputs = rng.randn(b, s, cfg.d_model).astype(np.float32)
    else:
        inputs = rng.randint(0, cfg.vocab_size, (b, s)).astype(np.int32)
    return {"inputs": jnp.asarray(inputs), "labels": jnp.asarray(labels)}


@pytest.mark.parametrize("arch", ARCHS)
def test_forward_and_loss(arch, mesh):
    cfg = reduced_config(get_config(arch))
    params = T.model_init(cfg, jax.random.PRNGKey(0))
    batch = _batch(cfg)
    with jax.set_mesh(mesh):
        logits, hidden, aux, _ = jax.jit(
            lambda p, b: T.forward(p, b["inputs"], cfg, mesh))(params, batch)
        loss, metrics = jax.jit(
            lambda p, b: T.lm_loss(p, b, cfg, mesh))(params, batch)
    assert logits.shape == (2, 16, cfg.vocab_size)
    assert hidden.shape == (2, 16, cfg.d_model)
    assert np.isfinite(np.asarray(logits, np.float32)).all()
    assert np.isfinite(float(loss))


@pytest.mark.parametrize("arch", ARCHS)
def test_train_step_decreases_loss(arch, mesh):
    cfg = reduced_config(get_config(arch))
    params = T.model_init(cfg, jax.random.PRNGKey(0))
    opt = make_optimizer(OptConfig(lr=5e-3))
    opt_state = opt.init(params)
    step = make_train_step(cfg, mesh, opt)
    batch = _batch(cfg)
    with jax.set_mesh(mesh):
        jstep = jax.jit(step, donate_argnums=(0, 1))
        losses = []
        for _ in range(4):
            params, opt_state, metrics = jstep(params, opt_state, batch)
            losses.append(float(metrics["loss"]))
    assert all(np.isfinite(losses)), losses
    assert losses[-1] < losses[0], losses   # same batch -> must descend


@pytest.mark.parametrize("arch", ARCHS)
def test_prefill_decode_consistency(arch, mesh):
    """Prefill then one decode step == forward over the full sequence.

    This is the strongest correctness property of the serving stack:
    KV/latent/state caches must reproduce the teacher-forced logits.
    """
    cfg = reduced_config(get_config(arch))
    params = T.model_init(cfg, jax.random.PRNGKey(1))
    b, s = 2, 12
    batch = _batch(cfg, b=b, s=s, seed=3)
    inputs = batch["inputs"]
    with jax.set_mesh(mesh):
        # full forward logits at the last position
        logits_full, _, _, _ = T.forward(params, inputs, cfg, mesh)
        # prefill on the first s-1 tokens, then decode token s-1
        prefix = inputs[:, : s - 1]
        _, cache, cur = prefill_step(params, prefix, cfg, mesh)
        # grow каждый cache's sequence dim to s (prefill caches cover s-1)
        def grow(x, shapes):
            return x
        state = {"cache": _pad_cache(cfg, cache, b, s - 1, s + 4),
                 "cur_len": cur}
        last = inputs[:, s - 1:]
        next_tok, _ = engine.decode_step(params, state, last, cfg, mesh)
    lf = np.asarray(logits_full[:, -1], np.float32)
    expected = lf.argmax(-1)
    got = np.asarray(next_tok)[:, 0]
    np.testing.assert_array_equal(got, expected)


def _pad_cache(cfg, cache, batch, cur_len, max_len):
    """Embed prefill caches (seq dim cur_len) into decode caches of
    max_len — attention/mla caches pad the seq dim; state caches pass."""
    target = T.cache_shapes(cfg, batch, max_len)

    def pad(x, t):
        x = jnp.asarray(x)
        if x.shape == t.shape:
            return x.astype(t.dtype)
        pads = [(0, ts - xs) for xs, ts in zip(x.shape, t.shape)]
        return jnp.pad(x, pads).astype(t.dtype)

    return jax.tree_util.tree_map(pad, cache, target)
