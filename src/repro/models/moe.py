"""Mixture-of-Experts transformer (deepseek-moe-16b, kimi-k2-1t).

Fine-grained MoE with shared experts, implemented with the
capacity-bucketed sort-dispatch pattern:

  1. router (fp32) -> top-k experts per token, renormalized weights;
  2. flatten (token, slot) pairs, sort by expert id (stable), rank within
     expert, drop beyond capacity C = ceil(T*k/E * capacity_factor);
  3. scatter tokens into an (E, C, d) buffer — under pjit this re-shards
     from token-sharded to expert-sharded layout (the all_to_all);
  4. batched expert SwiGLU einsum (E sharded over the `model` axis = EP);
  5. gather back, unsort, combine with router weights;
  6. shared experts run as an always-on dense MLP in parallel.

The dispatch tensors are O(T*k*d) — no (T, E, C) one-hots — so the pattern
scales to kimi's 384 experts at trillion-parameter size.  A Switch-style
load-balance auxiliary loss is returned alongside.
"""
from __future__ import annotations

import math
from typing import Any, Dict, Optional, Tuple

import jax
import jax.numpy as jnp

from .. import tuning
from ..configs.base import ArchConfig
from ..parallel import ctx
from .layers import (
    attention_decode, attn_init, chunked_xent, dense_init, mlp, mlp_init,
    rmsnorm, rmsnorm_init,
)
from .transformer import (
    _attention_dyn, _embed, attn_spec, init_cache, layer_windows, logits_fn,
)

Params = Dict[str, Any]


def moe_ffn_init(key, cfg: ArchConfig) -> Params:
    ks = jax.random.split(key, 5)
    d, ff, e = cfg.d_model, cfg.moe_d_ff, cfg.n_experts
    dt = cfg.p_dtype
    scale_in = 1.0 / math.sqrt(d)
    scale_out = 1.0 / math.sqrt(ff)
    p = {
        "router": (jax.random.normal(ks[0], (d, e)) * scale_in).astype(jnp.float32),
        "w_gate": (jax.random.normal(ks[1], (e, d, ff)) * scale_in).astype(dt),
        "w_up": (jax.random.normal(ks[2], (e, d, ff)) * scale_in).astype(dt),
        "w_down": (jax.random.normal(ks[3], (e, ff, d)) * scale_out).astype(dt),
    }
    if cfg.n_shared_experts:
        p["shared"] = mlp_init(ks[4], d, cfg.moe_d_ff * cfg.n_shared_experts, dt)
    return p


def moe_ffn(p: Params, cfg: ArchConfig,
            x: jnp.ndarray) -> Tuple[jnp.ndarray, jnp.ndarray]:
    """x: (B, S, d) -> (out, aux_loss).

    With an active mesh this takes the GShard-style shard_map path: local
    dispatch per data shard, explicit all_to_all over `model` (EP), local
    expert matmuls, reverse all_to_all, local combine.  Without a mesh
    (CPU smoke tests) the single-device dispatch below runs unchanged.
    """
    mesh = ctx.current_mesh()
    if (mesh is not None and "model" in mesh.axis_names
            and cfg.n_experts % mesh.shape["model"] == 0):
        return _moe_ffn_shardmap(p, cfg, x, mesh)
    return _moe_ffn_local(p, cfg, x)


def _moe_ffn_local(p: Params, cfg: ArchConfig,
                   x: jnp.ndarray) -> Tuple[jnp.ndarray, jnp.ndarray]:
    b, s, d = x.shape
    t = b * s
    k = cfg.experts_per_token
    e = cfg.n_experts
    cap = int(math.ceil(t * k / e * cfg.capacity_factor))
    cap = max(8, -(-cap // 8) * 8)

    xf = x.reshape(t, d)
    logits = (xf.astype(jnp.float32) @ p["router"]).astype(jnp.float32)  # (T, E)
    probs = jax.nn.softmax(logits, axis=-1)
    topv, topi = jax.lax.top_k(probs, k)                                  # (T, k)
    topv = topv / (topv.sum(axis=-1, keepdims=True) + 1e-9)

    # ---- load-balance aux (Switch): E * sum_e f_e * p_e
    me = probs.mean(axis=0)                                               # (E,)
    ce = jnp.zeros((e,), jnp.float32).at[topi.reshape(-1)].add(1.0) / (t * k)
    aux = e * jnp.sum(me * ce)

    # ---- sort-dispatch
    flat_e = topi.reshape(-1)                                             # (T*k,)
    flat_w = topv.reshape(-1)
    flat_tok = jnp.arange(t * k, dtype=jnp.int32) // k
    order = jnp.argsort(flat_e, stable=True)
    se = flat_e[order]
    starts = jnp.searchsorted(se, jnp.arange(e), side="left")
    rank = jnp.arange(t * k, dtype=jnp.int32) - starts[se].astype(jnp.int32)
    keep = rank < cap
    rank_c = jnp.minimum(rank, cap - 1)

    gathered = xf[flat_tok[order]] * keep[:, None].astype(xf.dtype)
    gathered = ctx.constrain(gathered, (ctx.DP, None))
    buf = jnp.zeros((e, cap, d), xf.dtype).at[se, rank_c].set(
        gathered, mode="drop")                                            # (E, C, d)
    # EP x DP: experts over `model`, capacity slots over the data axes —
    # the reshard from token layout to (E, C) layout is the all_to_all.
    buf = ctx.constrain(buf, ("model", ctx.DP, None))

    # ---- expert SwiGLU (EP: E sharded over `model`)
    dt = xf.dtype
    gate = jax.nn.silu(jnp.einsum("ecd,edf->ecf", buf, p["w_gate"].astype(dt)))
    up = jnp.einsum("ecd,edf->ecf", buf, p["w_up"].astype(dt))
    out_buf = jnp.einsum("ecf,efd->ecd", gate * up, p["w_down"].astype(dt))
    out_buf = ctx.constrain(out_buf, ("model", ctx.DP, None))

    # ---- return + combine
    vals = out_buf[se, rank_c] * keep[:, None].astype(dt)                 # (T*k, d)
    vals = ctx.constrain(vals, (ctx.DP, None))
    contrib = jnp.zeros((t, d), dt).at[flat_tok[order]].add(
        vals * flat_w[order, None].astype(dt))
    contrib = ctx.constrain(contrib, (ctx.DP, None))
    if "shared" in p:
        contrib = contrib + mlp(p["shared"], xf)
    return contrib.reshape(b, s, d), aux


def _moe_ffn_shardmap(p: Params, cfg: ArchConfig, x: jnp.ndarray, mesh
                      ) -> Tuple[jnp.ndarray, jnp.ndarray]:
    """GShard-pattern expert parallelism with explicit collectives.

    Tokens are data-sharded (replicated over `model`); experts are sharded
    over `model`.  Each shard dispatches its local tokens into an
    (E, C_local, d) capacity buffer, all_to_all's it so each device holds
    the slots of its own E/M experts, runs the expert SwiGLU locally, and
    reverses the exchange.  FSDP-sharded expert weights are all-gathered at
    entry by shard_map's in_specs (ZeRO-3 semantics)."""
    from jax.sharding import PartitionSpec as P

    b, s, d = x.shape
    e = cfg.n_experts
    k = cfg.experts_per_token
    m_sz = mesh.shape["model"]
    e_l = e // m_sz
    dp = tuple(a for a in ("pod", "data") if a in mesh.axis_names)
    dp_sz = 1
    for a in dp:
        dp_sz *= mesh.shape[a]
    b_l = max(1, b // dp_sz)
    t_l = b_l * s
    # Activations are replicated across `model`; each model-peer dispatches
    # its own 1/M slice of the local tokens (all_gather rebuilds the row at
    # the end).  Tiny decode batches skip the slicing (redundant dispatch is
    # cheaper than a ragged slice).
    slice_tokens = t_l % m_sz == 0 and t_l >= m_sz
    t_loc = t_l // m_sz if slice_tokens else t_l
    cf = tuning.get("capacity_factor") or cfg.capacity_factor
    if t_loc * k <= 512:
        cap = t_loc * k                     # decode: no-drop tiny buffer
    else:
        cap = int(math.ceil(t_loc * k / e * cf))
        cap = max(8, -(-cap // 8) * 8)

    def body(xl, router, wg, wu, wd):
        # xl: (b_l, s, d); wg/wu/wd: (e_l, ...) local experts
        xf = xl.reshape(t_l, d)
        if slice_tokens:
            midx = jax.lax.axis_index("model")
            xf = jax.lax.dynamic_slice_in_dim(xf, midx * t_loc, t_loc, axis=0)
        tl = t_loc
        logits = (xf.astype(jnp.float32) @ router).astype(jnp.float32)
        probs = jax.nn.softmax(logits, axis=-1)
        topv, topi = jax.lax.top_k(probs, k)
        topv = topv / (topv.sum(axis=-1, keepdims=True) + 1e-9)
        me = probs.mean(axis=0)
        ce = jnp.zeros((e,), jnp.float32).at[topi.reshape(-1)].add(1.0) / (tl * k)
        aux = e * jnp.sum(me * ce)
        aux_axes = dp + (("model",) if slice_tokens else ())
        if aux_axes:
            aux = jax.lax.pmean(aux, axis_name=aux_axes)

        flat_e = topi.reshape(-1)
        flat_w = topv.reshape(-1)
        flat_tok = jnp.arange(tl * k, dtype=jnp.int32) // k
        order = jnp.argsort(flat_e, stable=True)
        se = flat_e[order]
        starts = jnp.searchsorted(se, jnp.arange(e), side="left")
        rank = jnp.arange(tl * k, dtype=jnp.int32) - starts[se].astype(jnp.int32)
        keep = rank < cap
        rank_c = jnp.minimum(rank, cap - 1)
        gathered = xf[flat_tok[order]] * keep[:, None].astype(xf.dtype)
        buf = jnp.zeros((e, cap, d), xf.dtype).at[se, rank_c].set(
            gathered, mode="drop")
        # ---- dispatch a2a over the model axis (split==concat so the VJP is
        # the mirror-image all_to_all): block j -> peer j, receive block m
        buf = buf.reshape(m_sz, e_l, cap, d)
        buf = jax.lax.all_to_all(buf, "model", split_axis=0, concat_axis=0,
                                 tiled=False)
        buf = buf.transpose(1, 0, 2, 3).reshape(e_l, m_sz * cap, d)
        dt = xf.dtype
        gate = jax.nn.silu(jnp.einsum("ecd,edf->ecf", buf, wg.astype(dt)))
        up = jnp.einsum("ecd,edf->ecf", buf, wu.astype(dt))
        out_buf = jnp.einsum("ecf,efd->ecd", gate * up, wd.astype(dt))
        # ---- return a2a: (e_l, M, C, d) -> (M, e_l, C, d) -> (E, C, d)
        out_buf = out_buf.reshape(e_l, m_sz, cap, d).transpose(1, 0, 2, 3)
        out_buf = jax.lax.all_to_all(out_buf, "model", split_axis=0,
                                     concat_axis=0, tiled=False)
        out_buf = out_buf.reshape(e, cap, d)
        vals = out_buf[se, rank_c] * keep[:, None].astype(dt)
        contrib = jnp.zeros((tl, d), dt).at[flat_tok[order]].add(
            vals * flat_w[order, None].astype(dt))
        if slice_tokens:  # rebuild the full data-row (replicated over model)
            contrib = jax.lax.all_gather(contrib, "model", axis=0, tiled=True)
        return contrib.reshape(xl.shape), aux

    fn = jax.shard_map(
        body, mesh=mesh,
        in_specs=(P(dp if dp else None, None, None), P(), P("model",),
                  P("model",), P("model",)),
        out_specs=(P(dp if dp else None, None, None), P()),
        check_vma=False,
    )
    out, aux = fn(x, p["router"].astype(jnp.float32),
                  p["w_gate"], p["w_up"], p["w_down"])
    if "shared" in p:
        xf = x.reshape(b * s, d)
        out = out + mlp(p["shared"], xf).reshape(b, s, d)
    return out, aux


def init_moe_layer(key, cfg: ArchConfig) -> Params:
    ks = jax.random.split(key, 3)
    dt = cfg.p_dtype
    return {
        "ln1": rmsnorm_init(cfg.d_model, dt),
        "attn": attn_init(ks[0], attn_spec(cfg), dt),
        "ln2": rmsnorm_init(cfg.d_model, dt),
        "moe": moe_ffn_init(ks[1], cfg),
    }


def init_params(key, cfg: ArchConfig) -> Params:
    from .transformer import init_layer  # dense first block(s)

    kemb, kdense, kmoe, kfin = jax.random.split(key, 4)
    dt = cfg.p_dtype
    n_moe = cfg.n_layers - cfg.first_dense_layers
    p: Params = {
        "embed": dense_init(kemb, cfg.vocab, cfg.d_model, dt),
        "ln_f": rmsnorm_init(cfg.d_model, dt),
    }
    if cfg.first_dense_layers:
        dk = jax.random.split(kdense, cfg.first_dense_layers)
        p["dense_layers"] = jax.vmap(lambda k: init_layer(k, cfg))(dk)
    mk = jax.random.split(kmoe, n_moe)
    p["moe_layers"] = jax.vmap(lambda k: init_moe_layer(k, cfg))(mk)
    return p


def forward(params: Params, cfg: ArchConfig, tokens: jnp.ndarray,
            q_chunk: int = 512, remat: bool = True) -> Tuple[jnp.ndarray, jnp.ndarray]:
    b, s = tokens.shape
    x = _embed(params, cfg, tokens)
    positions = jnp.broadcast_to(jnp.arange(s, dtype=jnp.int32)[None], (b, s))
    spec = attn_spec(cfg)
    zero_win = jnp.int32(0)

    if cfg.first_dense_layers:
        def dense_body(x, layer_p):
            h = rmsnorm(layer_p["ln1"], x)
            h = _attention_dyn(layer_p["attn"], spec, h, positions, zero_win, q_chunk)
            x = x + h
            x = x + mlp(layer_p["mlp"], rmsnorm(layer_p["ln2"], x))
            return x, None
        if remat:
            dense_body = tuning.remat_wrap(dense_body)
        x, _ = jax.lax.scan(dense_body, x, params["dense_layers"])

    def moe_body(carry, layer_p):
        x, aux = carry
        h = rmsnorm(layer_p["ln1"], x)
        h = _attention_dyn(layer_p["attn"], spec, h, positions, zero_win, q_chunk)
        x = x + h
        h, a = moe_ffn(layer_p["moe"], cfg, rmsnorm(layer_p["ln2"], x))
        x = x + h
        if tuning.get("seq_shard_mlp"):
            x = ctx.constrain(x, (ctx.DP, "model", None))
        return (x, aux + a), None

    if remat:
        moe_body = tuning.remat_wrap(moe_body)
    (x, aux), _ = jax.lax.scan(moe_body, (x, jnp.float32(0.0)), params["moe_layers"])
    return rmsnorm(params["ln_f"], x), aux / max(1, cfg.n_layers)


def loss_fn(params: Params, cfg: ArchConfig, batch: Dict[str, jnp.ndarray],
            q_chunk: int = 512, aux_weight: float = 0.01) -> jnp.ndarray:
    hidden, aux = forward(params, cfg, batch["tokens"], q_chunk=q_chunk)
    emb = params["embed"]
    return chunked_xent(hidden, emb, batch["labels"]) + aux_weight * aux


def decode_step(params: Params, cfg: ArchConfig, cache: Params,
                tokens: jnp.ndarray, pos: jnp.ndarray) -> Tuple[jnp.ndarray, Params]:
    """One-token MoE decode; caches are (L, B, S, Kv, D) across *all* layers
    (dense first, then MoE layers, in order)."""
    x = _embed(params, cfg, tokens)
    spec = attn_spec(cfg)
    nd = cfg.first_dense_layers
    ck_all, cv_all = cache["k"], cache["v"]

    new_k, new_v = [], []
    if nd:
        def dense_body(x, xs):
            layer_p, ck, cv = xs
            h = rmsnorm(layer_p["ln1"], x)
            h, ck, cv = attention_decode(layer_p["attn"], spec, h, ck, cv, pos)
            x = x + h
            x = x + mlp(layer_p["mlp"], rmsnorm(layer_p["ln2"], x))
            return x, (ck, cv)
        x, (dk, dv) = jax.lax.scan(
            dense_body, x, (params["dense_layers"], ck_all[:nd], cv_all[:nd]))
        new_k.append(dk); new_v.append(dv)

    def moe_body(x, xs):
        layer_p, ck, cv = xs
        h = rmsnorm(layer_p["ln1"], x)
        h, ck, cv = attention_decode(layer_p["attn"], spec, h, ck, cv, pos)
        x = x + h
        h, _ = moe_ffn(layer_p["moe"], cfg, rmsnorm(layer_p["ln2"], x))
        return x + h, (ck, cv)

    x, (mk, mv) = jax.lax.scan(
        moe_body, x, (params["moe_layers"], ck_all[nd:], cv_all[nd:]))
    new_k.append(mk); new_v.append(mv)
    x = rmsnorm(params["ln_f"], x)
    logits = logits_fn(params, cfg, x[:, 0])
    return logits, {"k": jnp.concatenate(new_k), "v": jnp.concatenate(new_v)}
