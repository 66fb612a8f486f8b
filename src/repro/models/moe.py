"""Mixture-of-Experts layer — DBCSR block-sparse multiply + densification,
re-cast onto a modern workload.

The token->expert dispatch defines a block-sparse (token-block x expert)
matrix multiply; *densification* (paper section III) is the grouped-GEMM
trick: gather each expert's tokens into one contiguous capacity buffer
so the expert compute becomes a batch of large dense GEMMs.  The
'blocked' path keeps per-block small GEMMs (LIBCUSMM regime) and exists
for the paper's blocked-vs-densified comparison (benchmarks/bench_densify).

Distribution (expert parallelism): activations are data-sharded and
replicated over the 'model' axis; expert weights are sharded over
'model' (E_loc = E / tp experts per device).  Because every device
already holds all of its data-shard's tokens, dispatch is LOCAL — each
device gathers tokens routed to *its* experts, runs the grouped GEMM,
scatters partial outputs, and a single psum over 'model' combines them
(the same reduction a row-parallel dense FFN needs, so EP costs no
extra collective vs TP at this layout).  The layer is a shard_map
island inside the otherwise GSPMD-auto program.

Capacity ranking is sort-based (argsort over expert ids + group-start
offsets), never materialising the (T, E, C) one-hot dispatch tensor of
GShard-style einsum MoE — at DeepSeek-V3 scale that tensor would be
~GBs/device while the sort is a few MB.
"""
from __future__ import annotations

import dataclasses
import functools
from typing import Dict, Optional, Tuple

import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P

from .common import ParamDef, act_fn

__all__ = ["moe_defs", "moe_apply"]


def moe_defs(cfg) -> Dict[str, ParamDef]:
    d, f = cfg.d_model, cfg.moe_d_ff
    e = cfg.n_experts
    # moe_fsdp (DeepSeek-671B scale): expert weights additionally shard
    # dim 1 over the data axes — TP-only storage would need ~84 GB/chip.
    # The weights are re-gathered per layer inside moe_local (classic
    # weight-gathered FSDP; the reverse pass reduce-scatters the grads).
    fs = ("pod", "data") if cfg.moe_fsdp else None
    defs = {
        "router": ParamDef((d, e), P(None, None), "normal"),
        "w_gate": ParamDef((e, d, f), P("model", fs, None)),
        "w_up": ParamDef((e, d, f), P("model", fs, None)),
        "w_down": ParamDef((e, f, d), P("model", fs, None)),
    }
    if cfg.n_shared_experts:
        fs = cfg.moe_d_ff * cfg.n_shared_experts
        defs["shared"] = {
            "w_gate": ParamDef((d, fs), P(None, "model")),
            "w_up": ParamDef((d, fs), P(None, "model")),
            "w_down": ParamDef((fs, d), P("model", None)),
        }
    return defs


def _capacity(n_tokens: int, cfg) -> int:
    c = int(n_tokens * cfg.top_k * cfg.capacity_factor / cfg.n_experts)
    return max(8, -(-c // 8) * 8)  # round up to 8 for TPU-friendly shapes


def _rank_within_expert(flat_eid: jax.Array, n_experts: int):
    """Position of each (token, slot) in its expert's queue.

    Sort-based: O(Tk log Tk) int ops instead of a (Tk, E) one-hot cumsum.
    """
    tk = flat_eid.shape[0]
    order = jnp.argsort(flat_eid)                     # stable
    sorted_eid = flat_eid[order]
    starts = jnp.searchsorted(sorted_eid, jnp.arange(n_experts))
    rank_sorted = jnp.arange(tk) - starts[sorted_eid]
    inv = jnp.zeros_like(order).at[order].set(jnp.arange(tk))
    return rank_sorted[inv]


def moe_local(
    params: Dict,
    x: jax.Array,           # (T, d) — this device's data-shard tokens
    cfg,
    *,
    tp_axis: str = "model",
    local_path: str = "densified",   # densified | blocked
    block_c: int = 64,
    fsdp_axes=None,
    token_gathered: bool = False,    # x is already all-token (partial path)
) -> Tuple[jax.Array, jax.Array]:
    """Per-device MoE body (inside shard_map). Returns (partial_out, aux)."""
    t, d = x.shape
    e, e_loc = cfg.n_experts, cfg.n_experts // jax.lax.axis_size(tp_axis)
    k = cfg.top_k
    cap = _capacity(t, cfg)
    m = jax.lax.axis_index(tp_axis)
    act = act_fn(cfg.act)

    # partial-compute crossover (see moe_apply): token activations are
    # cheaper to move than FSDP weight shards when T_all*d << 3*E_loc*d*f
    partial_compute = token_gathered

    # ---- router (f32 for numerics) -----------------------------------
    logits = jnp.einsum("td,de->te", x.astype(jnp.float32),
                        params["router"].astype(jnp.float32))
    if cfg.router == "sigmoid":      # DeepSeek-V3 style
        scores = jax.nn.sigmoid(logits)
    else:
        scores = jax.nn.softmax(logits, axis=-1)
    gate_w, eid = jax.lax.top_k(scores, k)            # (T, k)
    gate_w = gate_w / jnp.maximum(gate_w.sum(-1, keepdims=True), 1e-9)

    # ---- capacity ranking & local dispatch indices --------------------
    flat_eid = eid.reshape(-1)                         # (T*k,)
    pos = _rank_within_expert(flat_eid, e)             # (T*k,)
    e_local = flat_eid - m * e_loc
    valid = (e_local >= 0) & (e_local < e_loc) & (pos < cap)
    # invalid entries -> OOB so scatter/gather drop them
    e_ix = jnp.where(valid, e_local, e_loc)
    p_ix = jnp.where(valid, pos, cap)

    # ---- densify: gather tokens into the capacity buffer --------------
    # one scatter per top-k slot: materialises (T, d) per slot instead
    # of one (T*k, d) tensor — 8x smaller peak at DeepSeek's k=8
    e_ix_k = e_ix.reshape(t, k)
    p_ix_k = p_ix.reshape(t, k)
    buf = jnp.zeros((e_loc, cap, d), x.dtype)
    for kk in range(k):
        buf = buf.at[e_ix_k[:, kk], p_ix_k[:, kk]].set(x, mode="drop")

    # ---- expert compute (weights arrive model-sharded: (e_loc, d, f)) --
    wg, wu, wd = params["w_gate"], params["w_up"], params["w_down"]
    if fsdp_axes and not partial_compute:
        # weight-gathered FSDP: dim 1 was stored sharded over the data
        # axes; gather it for this layer's compute (AD reduce-scatters)
        wg = jax.lax.all_gather(wg, fsdp_axes, axis=1, tiled=True)
        wu = jax.lax.all_gather(wu, fsdp_axes, axis=1, tiled=True)
        wd = jax.lax.all_gather(wd, fsdp_axes, axis=1, tiled=True)

    def expert_ffn(tokens):  # (e_loc, C, d) -> (e_loc, C, d)
        if partial_compute:
            # d (and f for w_down) stay sharded over the fsdp axes: each
            # shard contracts its slice and the small (E_loc, C, f)
            # activations are psum'd — the decode-side replacement for
            # the 1.4 GB/layer weight gathers (EXPERIMENTS.md §Perf).
            nd = jax.lax.axis_size(fsdp_axes)
            ix = jax.lax.axis_index(fsdp_axes)
            dsl = d // nd
            tok_slice = jax.lax.dynamic_slice_in_dim(
                tokens, ix * dsl, dsl, axis=2)
            g = jnp.einsum("ecd,edf->ecf", tok_slice, wg.astype(tokens.dtype))
            u = jnp.einsum("ecd,edf->ecf", tok_slice, wu.astype(tokens.dtype))
            g = jax.lax.psum(g, fsdp_axes)
            u = jax.lax.psum(u, fsdp_axes)
            h = act(g) * u
            f_all = h.shape[-1]
            fsl = f_all // nd
            h_slice = jax.lax.dynamic_slice_in_dim(h, ix * fsl, fsl, axis=2)
            # partial over f: the combining psum over (tp, fsdp) happens
            # in moe_apply's body
            return jnp.einsum("ecf,efd->ecd", h_slice,
                              wd.astype(tokens.dtype))
        g = jnp.einsum("ecd,edf->ecf", tokens, wg.astype(tokens.dtype))
        u = jnp.einsum("ecd,edf->ecf", tokens, wu.astype(tokens.dtype))
        h = act(g) * u
        return jnp.einsum("ecf,efd->ecd", h, wd.astype(tokens.dtype))

    if local_path == "densified":
        buf_out = expert_ffn(buf)
    elif local_path == "blocked":
        # DBCSR 'blocked' regime: the capacity buffer is processed in
        # small token-blocks, each a separate small GEMM (stack entries).
        nb = cap // block_c
        blocks = buf.reshape(e_loc, nb, block_c, d)

        def per_block(blk):  # (e_loc, block_c, d)
            return expert_ffn(blk)

        buf_out = jax.lax.map(per_block, blocks.transpose(1, 0, 2, 3))
        buf_out = buf_out.transpose(1, 0, 2, 3).reshape(e_loc, cap, d)
    else:
        raise ValueError(local_path)

    # ---- combine: gather back, weight, sum over the k slots ----------
    valid_k = valid.reshape(t, k)
    out = jnp.zeros((t, d), buf_out.dtype)
    for kk in range(k):
        g = buf_out.at[e_ix_k[:, kk], p_ix_k[:, kk]].get(
            mode="fill", fill_value=0)                       # (T, d)
        w_ = (gate_w[:, kk] * valid_k[:, kk]).astype(g.dtype)
        out = out + g * w_[:, None]

    # ---- shared experts (TP within the same shard_map) ----------------
    if cfg.n_shared_experts:
        sh = params["shared"]
        g = jnp.einsum("td,df->tf", x, sh["w_gate"].astype(x.dtype))
        u = jnp.einsum("td,df->tf", x, sh["w_up"].astype(x.dtype))
        out = out + jnp.einsum("tf,fd->td", act(g) * u,
                               sh["w_down"].astype(x.dtype))

    # ---- aux load-balancing loss (Switch style) ------------------------
    me = jnp.mean(jax.nn.one_hot(eid[:, 0], e, dtype=jnp.float32), axis=0)
    ce = jnp.mean(scores, axis=0)
    aux = e * jnp.sum(me * ce)

    return out, aux


def moe_apply(
    params: Dict,
    x: jax.Array,            # (B, S, d) data-sharded, model-replicated
    cfg,
    *,
    mesh,
    dp_axes=("pod", "data"),
    tp_axis: str = "model",
    local_path: str = "densified",
) -> Tuple[jax.Array, jax.Array]:
    """Full MoE layer. Returns (out (B,S,d), aux_loss scalar)."""
    b, s, d = x.shape
    dp_axes = tuple(a for a in dp_axes if a in mesh.shape)
    n_dp = 1
    for a in dp_axes:
        n_dp *= mesh.shape[a]
    if b % max(n_dp, 1) != 0:
        # batch can't cover the data axes (e.g. long_500k decode, B=1):
        # tokens stay replicated over them; compute is redundant across
        # data shards but correct, and B=1 decode is latency-bound anyway.
        dp_axes = ()
    fsdp = (tuple(a for a in ("pod", "data") if a in mesh.shape)
            if cfg.moe_fsdp else None) or None

    pspec = {
        "router": P(None, None),
        "w_gate": P(tp_axis, fsdp, None),
        "w_up": P(tp_axis, fsdp, None),
        "w_down": P(tp_axis, fsdp, None),
    }
    if cfg.n_shared_experts:
        pspec["shared"] = {"w_gate": P(None, tp_axis),
                           "w_up": P(None, tp_axis),
                           "w_down": P(tp_axis, None)}

    # partial-compute crossover: move tokens (T_all x d) instead of
    # gathering weights (3 x E_loc x d x f) when tokens are much smaller
    # — decisive for decode (T_all ~ 128 vs 44M weight elements/layer).
    n_fsdp = 1
    if cfg.moe_fsdp:
        for a in ("pod", "data"):
            n_fsdp *= mesh.shape.get(a, 1)
    t_all = b * s * (1 if dp_axes else 1)  # global tokens this step
    use_partial = (cfg.moe_fsdp and cfg.moe_small_t_partial
                   and n_fsdp > 1
                   and t_all * 8 < 3 * (cfg.n_experts // mesh.shape[tp_axis])
                   * cfg.moe_d_ff)

    def body(p, xb):
        tloc = xb.shape[0] * xb.shape[1]
        xt = xb.reshape(tloc, d)
        fsdp_b = fsdp
        if use_partial:
            if dp_axes:  # distinct tokens per data shard: gather them
                xt = jax.lax.all_gather(xt, fsdp_b, axis=0, tiled=True)
            out, aux = moe_local(p, xt, cfg, tp_axis=tp_axis,
                                 local_path=local_path, fsdp_axes=fsdp_b,
                                 token_gathered=True)
            out = jax.lax.psum(out, (tp_axis,) + tuple(fsdp_b))
            if dp_axes:  # slice back this shard's tokens
                ix = jax.lax.axis_index(fsdp_b)
                out = jax.lax.dynamic_slice_in_dim(out, ix * tloc, tloc, 0)
        else:
            out, aux = moe_local(p, xt, cfg, tp_axis=tp_axis,
                                 local_path=local_path, fsdp_axes=fsdp_b)
            out = jax.lax.psum(out, tp_axis)
        aux = jax.lax.pmean(aux, tp_axis)
        return out.reshape(xb.shape), aux.reshape(1)

    dp_part = dp_axes if dp_axes else None
    # check_vma=False: with B=1 decode the tokens are replicated over the
    # data axes while FSDP weight-gathers still run over them — outputs
    # are replicated by construction but the static analysis can't see it.
    out, aux = jax.shard_map(
        body,
        mesh=mesh,
        in_specs=(pspec, P(dp_part, None, None)),
        out_specs=(P(dp_part, None, None), P(dp_part)),
        check_vma=False,
    )(params, x)
    return out, jnp.mean(aux)
