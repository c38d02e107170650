"""Shared neural building blocks for every architecture in the pool.

Everything is written against plain pytrees (dicts of jnp arrays) — no
flax/haiku dependency — so parameter sharding specs can be attached by name
pattern in ``parallel/sharding.py`` and models scan cleanly over stacked
layer parameters.

Conventions:
  * params are created in ``param_dtype`` (fp32 by default) and cast to
    ``dtype`` (bf16 on TPU) at use — the usual mixed-precision recipe;
  * attention uses blockwise (memory-efficient) softmax over query chunks so
    (B, H, S, S) score tensors are never materialized at 32k sequence;
  * decode paths take a KV cache laid out (B, S_max, n_kv, head_dim) and a
    scalar position.
"""
from __future__ import annotations

import dataclasses
import math
from typing import Any, Dict, Optional, Tuple

import jax
import jax.numpy as jnp

from .. import tuning
from ..parallel import ctx

Params = Dict[str, Any]

# --------------------------------------------------------------------------
# initializers
# --------------------------------------------------------------------------

def _normal(key, shape, scale, dtype):
    return (jax.random.normal(key, shape) * scale).astype(dtype)


def dense_init(key, d_in: int, d_out: int, dtype) -> jnp.ndarray:
    return _normal(key, (d_in, d_out), 1.0 / math.sqrt(d_in), dtype)


# --------------------------------------------------------------------------
# norms
# --------------------------------------------------------------------------

def rmsnorm_init(d: int, dtype) -> Params:
    return {"scale": jnp.ones((d,), dtype=dtype)}


def rmsnorm(p: Params, x: jnp.ndarray, eps: float = 1e-6) -> jnp.ndarray:
    dt = x.dtype
    if tuning.get("act_bf16") and dt == jnp.bfloat16:
        # f32 only inside the variance reduction (fusion boundary is the
        # tiny (B,S,1) stat); the normalize/scale applies in bf16 — avoids
        # materializing any f32 copy of the residual stream.
        var = jnp.mean(jnp.square(x.astype(jnp.float32)), axis=-1,
                       keepdims=True)
        inv = jax.lax.rsqrt(var + eps).astype(dt)
        return x * inv * p["scale"].astype(dt)
    xf = x.astype(jnp.float32)
    var = jnp.mean(xf * xf, axis=-1, keepdims=True)
    out = xf * jax.lax.rsqrt(var + eps)
    return (out * p["scale"].astype(jnp.float32)).astype(dt)


# --------------------------------------------------------------------------
# rotary embeddings
# --------------------------------------------------------------------------

def rope(x: jnp.ndarray, positions: jnp.ndarray, theta: float) -> jnp.ndarray:
    """x: (..., S, H, D); positions: (..., S) int32."""
    d = x.shape[-1]
    half = d // 2
    freq = theta ** (-jnp.arange(0, half, dtype=jnp.float32) / half)
    angles = positions[..., None].astype(jnp.float32) * freq  # (..., S, half)
    cos = jnp.cos(angles)[..., None, :]  # broadcast over heads
    sin = jnp.sin(angles)[..., None, :]
    x1, x2 = x[..., :half], x[..., half:]
    out = jnp.concatenate([x1 * cos - x2 * sin, x1 * sin + x2 * cos], axis=-1)
    return out.astype(x.dtype)


# --------------------------------------------------------------------------
# attention
# --------------------------------------------------------------------------

@dataclasses.dataclass(frozen=True)
class AttnSpec:
    d_model: int
    n_heads: int
    n_kv: int
    head_dim: int
    qk_norm: bool = False
    rope_theta: float = 10000.0
    causal: bool = True
    # sliding window size; None = full attention.  Per-layer local/global
    # selection is handled by the caller via the `window` argument override.
    window: Optional[int] = None


def attn_init(key, spec: AttnSpec, dtype) -> Params:
    ks = jax.random.split(key, 4)
    d, h, kvh, hd = spec.d_model, spec.n_heads, spec.n_kv, spec.head_dim
    p = {
        "wq": dense_init(ks[0], d, h * hd, dtype).reshape(d, h, hd),
        "wk": dense_init(ks[1], d, kvh * hd, dtype).reshape(d, kvh, hd),
        "wv": dense_init(ks[2], d, kvh * hd, dtype).reshape(d, kvh, hd),
        "wo": dense_init(ks[3], h * hd, d, dtype).reshape(h, hd, d),
    }
    if spec.qk_norm:
        p["q_norm"] = rmsnorm_init(hd, dtype)
        p["k_norm"] = rmsnorm_init(hd, dtype)
    return p


def _qkv(p: Params, spec: AttnSpec, x: jnp.ndarray, positions: jnp.ndarray):
    dt = x.dtype
    # ZeRO-3: gather FSDP-sharded weights at use, to their TP-only layout
    # (one layer's weights live gathered at a time inside the layer scan)
    wq = ctx.constrain(p["wq"].astype(dt), (None, "model", None))
    wk = ctx.constrain(p["wk"].astype(dt), (None, "model", None))
    wv = ctx.constrain(p["wv"].astype(dt), (None, "model", None))
    q = jnp.einsum("bsd,dhk->bshk", x, wq)
    k = jnp.einsum("bsd,dhk->bshk", x, wk)
    v = jnp.einsum("bsd,dhk->bshk", x, wv)
    if spec.qk_norm:
        q = rmsnorm(p["q_norm"], q)
        k = rmsnorm(p["k_norm"], k)
    q = rope(q, positions, spec.rope_theta)
    k = rope(k, positions, spec.rope_theta)
    return q, k, v


def _repeat_kv(k: jnp.ndarray, groups: int) -> jnp.ndarray:
    """(B, S, Kv, D) -> (B, S, Kv*groups, D) by repeat (GQA share)."""
    if groups == 1:
        return k
    b, s, kv, d = k.shape
    return jnp.broadcast_to(
        k[:, :, :, None, :], (b, s, kv, groups, d)).reshape(b, s, kv * groups, d)


def attention(
    p: Params,
    spec: AttnSpec,
    x: jnp.ndarray,
    positions: jnp.ndarray,
    window: Optional[int] = None,
    q_chunk: int = 512,
    cross_kv: Optional[Tuple[jnp.ndarray, jnp.ndarray]] = None,
) -> jnp.ndarray:
    """Blockwise-softmax multi-head attention (training / prefill path).

    Scans over query chunks; each step materializes only a
    (B, H, q_chunk, S) score tile.  ``window`` enables sliding-window
    (local) masking; ``cross_kv`` switches to encoder-decoder cross
    attention (no causal mask, externally supplied K/V).
    """
    b, s, d = x.shape
    spec_window = window if window is not None else spec.window
    if cross_kv is None:
        q, k, v = _qkv(p, spec, x, positions)
    else:
        dt = x.dtype
        q = jnp.einsum("bsd,dhk->bshk", x, p["wq"].astype(dt))
        if spec.qk_norm:
            q = rmsnorm(p["q_norm"], q)
        k, v = cross_kv
    groups = spec.n_heads // spec.n_kv
    k = _repeat_kv(k, groups)
    v = _repeat_kv(v, groups)
    scale = 1.0 / math.sqrt(spec.head_dim)
    kv_pos = jnp.arange(k.shape[1])

    q_chunk = min(q_chunk, s)
    n_chunks = max(1, s // q_chunk)
    pad = n_chunks * q_chunk != s
    if pad:  # ragged tail: fall back to a single chunk
        q_chunk, n_chunks = s, 1

    assert positions.ndim == 2, "positions must be (B, S)"
    qs = q.reshape(b, n_chunks, q_chunk, spec.n_heads, spec.head_dim)
    pos_chunks = positions.reshape(b, n_chunks, q_chunk)

    def one_chunk(q_i, pos_i):
        # q_i: (B, c, H, D); scores vs all keys: (B, H, c, S)
        scores = jnp.einsum("bchk,bshk->bhcs", q_i, k).astype(jnp.float32) * scale
        if cross_kv is None and spec.causal:
            cmask = pos_i[:, None, :, None] >= kv_pos[None, None, None, :]
            if spec_window is not None:
                cmask &= (pos_i[:, None, :, None]
                          - kv_pos[None, None, None, :] < spec_window)
            scores = jnp.where(cmask, scores, -1e30)
        out = jax.nn.softmax(scores, axis=-1).astype(q_i.dtype)
        return jnp.einsum("bhcs,bshk->bchk", out, v)

    if n_chunks == 1:
        o = one_chunk(qs[:, 0], pos_chunks[:, 0])[:, None]
    else:
        def body(_, xs):
            q_i, pos_i = xs
            return None, one_chunk(q_i, pos_i)
        _, o = jax.lax.scan(
            body, None,
            (qs.transpose(1, 0, 2, 3, 4), pos_chunks.transpose(1, 0, 2)),
        )
        o = o.transpose(1, 0, 2, 3, 4)
    o = o.reshape(b, s, spec.n_heads, spec.head_dim)
    wo = ctx.constrain(p["wo"].astype(o.dtype), ("model", None, None))
    return jnp.einsum("bshk,hkd->bsd", o, wo)


def attention_decode(
    p: Params,
    spec: AttnSpec,
    x: jnp.ndarray,             # (B, 1, d)
    cache_k: jnp.ndarray,       # (B, S_max, n_kv, D)
    cache_v: jnp.ndarray,
    pos: jnp.ndarray,           # scalar int32 — current position
    window: Optional[int] = None,
) -> Tuple[jnp.ndarray, jnp.ndarray, jnp.ndarray]:
    """Single-token decode with KV-cache append.

    Default path: dense reduction over the cache (XLA partitions it).  With
    the ``flash_decode`` tuning knob and an active mesh, the sequence-
    sharded cache is handled by an explicit shard_map: per-shard partial
    (max, num, den) softmax stats combined with two tiny psums — the
    flash-decoding pattern — so the cache is NEVER all-gathered.
    """
    mesh = ctx.current_mesh()
    if (tuning.get("flash_decode") and mesh is not None
            and _flash_applicable(x, cache_k, mesh)):
        return _attention_decode_flash(p, spec, x, cache_k, cache_v, pos,
                                       window, mesh)
    b = x.shape[0]
    positions = jnp.full((b, 1), pos, dtype=jnp.int32)
    q, k_new, v_new = _qkv(p, spec, x, positions)
    cache_k = jax.lax.dynamic_update_slice(
        cache_k, k_new.astype(cache_k.dtype), (0, pos, 0, 0))
    cache_v = jax.lax.dynamic_update_slice(
        cache_v, v_new.astype(cache_v.dtype), (0, pos, 0, 0))
    groups = spec.n_heads // spec.n_kv
    k = _repeat_kv(cache_k.astype(x.dtype), groups)
    v = _repeat_kv(cache_v.astype(x.dtype), groups)
    scale = 1.0 / math.sqrt(spec.head_dim)
    scores = jnp.einsum("bchk,bshk->bhcs", q, k).astype(jnp.float32) * scale
    kv_pos = jnp.arange(k.shape[1])
    mask = kv_pos[None, None, None, :] <= pos
    w = window if window is not None else spec.window
    if w is not None:
        mask &= kv_pos[None, None, None, :] > pos - w
    scores = jnp.where(mask, scores, -1e30)
    # numerically-stable softmax, written as separable (max, lse) so the
    # reduction re-associates across sequence shards:
    mx = jnp.max(scores, axis=-1, keepdims=True)
    ex = jnp.exp(scores - mx)
    den = jnp.sum(ex, axis=-1, keepdims=True)
    probs = (ex / den).astype(x.dtype)
    o = jnp.einsum("bhcs,bshk->bchk", probs, v)
    wo = ctx.constrain(p["wo"].astype(o.dtype), ("model", None, None))
    out = jnp.einsum("bshk,hkd->bsd", o, wo)
    return out, cache_k, cache_v




def _flash_applicable(x, cache_k, mesh) -> bool:
    m = mesh.shape.get("model", 1)
    dp = 1
    for a in ("pod", "data"):
        if a in mesh.axis_names:
            dp *= mesh.shape[a]
    return (cache_k.shape[1] % m == 0 and x.shape[0] % dp == 0
            and "model" in mesh.axis_names)


def _attention_decode_flash(p, spec, x, cache_k, cache_v, pos, window, mesh):
    """shard_map flash-decoding: cache stays sequence-sharded over `model`;
    each shard computes masked partial softmax stats; two psums of
    (B, H)-sized stats produce the exact softmax.  The token's new K/V is
    written only by the owning shard."""
    from jax.sharding import PartitionSpec as P

    b = x.shape[0]
    s_max = cache_k.shape[1]
    m_sz = mesh.shape["model"]
    s_loc = s_max // m_sz
    dp = tuple(a for a in ("pod", "data") if a in mesh.axis_names)
    positions = jnp.full((b, 1), pos, dtype=jnp.int32)
    q, k_new, v_new = _qkv(p, spec, x, positions)
    groups = spec.n_heads // spec.n_kv
    scale = 1.0 / math.sqrt(spec.head_dim)
    w = window if window is not None else spec.window

    def body(q_l, kn_l, vn_l, ck_l, cv_l):
        # q_l: (B_l, 1, H, D); ck_l: (B_l, s_loc, K, D)
        sidx = jax.lax.axis_index("model")
        base = sidx * s_loc
        # owning shard writes the new token's K/V at local offset
        off = jnp.clip(pos - base, 0, s_loc - 1)
        owns = (pos >= base) & (pos < base + s_loc)
        upd_k = jax.lax.dynamic_update_slice(
            ck_l, kn_l.astype(ck_l.dtype), (0, off, 0, 0))
        upd_v = jax.lax.dynamic_update_slice(
            cv_l, vn_l.astype(cv_l.dtype), (0, off, 0, 0))
        ck_l = jnp.where(owns, upd_k, ck_l)
        cv_l = jnp.where(owns, upd_v, cv_l)
        k = _repeat_kv(ck_l.astype(q_l.dtype), groups)
        v = _repeat_kv(cv_l.astype(q_l.dtype), groups)
        kv_pos = base + jnp.arange(s_loc)
        scores = jnp.einsum("bchk,bshk->bhcs", q_l * jnp.asarray(scale, q_l.dtype), k,
                            preferred_element_type=jnp.float32)
        mask = kv_pos[None, None, None, :] <= pos
        if w is not None:
            mask &= kv_pos[None, None, None, :] > pos - w
        scores = jnp.where(mask, scores, -1e30)
        mx_l = jnp.max(scores, axis=-1)                      # (B,H,1)
        ex = jnp.exp(scores - mx_l[..., None])
        den_l = jnp.sum(ex, axis=-1)
        num_l = jnp.einsum("bhcs,bshk->bchk", ex.astype(q_l.dtype), v)
        # exact combine: rescale by exp(mx_l - global max), then psum
        mx_g = jax.lax.pmax(mx_l, "model")
        corr = jnp.exp(mx_l - mx_g)                          # (B,H,1)
        num = jax.lax.psum(
            num_l * jnp.swapaxes(corr, 1, 2)[..., None].astype(num_l.dtype),
            "model")
        den = jax.lax.psum(den_l * corr, "model")
        o = num / jnp.swapaxes(den, 1, 2)[..., None].astype(num.dtype)
        return o, ck_l, cv_l

    dps = dp if dp else None
    fn = jax.shard_map(
        body, mesh=mesh,
        in_specs=(P(dps, None, None, None), P(dps, None, None, None),
                  P(dps, None, None, None), P(dps, "model", None, None),
                  P(dps, "model", None, None)),
        out_specs=(P(dps, None, None, None), P(dps, "model", None, None),
                   P(dps, "model", None, None)),
        check_vma=False,
    )
    o, cache_k, cache_v = fn(q, k_new, v_new, cache_k, cache_v)
    o = o.reshape(b, 1, spec.n_heads, spec.head_dim)
    wo = ctx.constrain(p["wo"].astype(o.dtype), ("model", None, None))
    out = jnp.einsum("bshk,hkd->bsd", o, wo)
    return out, cache_k, cache_v


# --------------------------------------------------------------------------
# MLP (SwiGLU)
# --------------------------------------------------------------------------

def mlp_init(key, d: int, ff: int, dtype, variant: str = "swiglu") -> Params:
    ks = jax.random.split(key, 3)
    if variant == "gelu":
        return {
            "w_up": dense_init(ks[1], d, ff, dtype),
            "w_down": dense_init(ks[2], ff, d, dtype),
        }
    return {
        "w_gate": dense_init(ks[0], d, ff, dtype),
        "w_up": dense_init(ks[1], d, ff, dtype),
        "w_down": dense_init(ks[2], ff, d, dtype),
    }


def mlp(p: Params, x: jnp.ndarray) -> jnp.ndarray:
    dt = x.dtype
    w_up = ctx.constrain(p["w_up"].astype(dt), (None, "model"))
    w_down = ctx.constrain(p["w_down"].astype(dt), ("model", None))
    if "w_gate" in p:  # SwiGLU
        w_gate = ctx.constrain(p["w_gate"].astype(dt), (None, "model"))
        gate = jax.nn.silu(x @ w_gate)
        return (gate * (x @ w_up)) @ w_down
    u = x @ w_up
    if tuning.get("act_bf16") and u.dtype == jnp.bfloat16:
        # dtype-clean tanh gelu (python-float constants stay weakly typed)
        h = 0.5 * u * (1.0 + jnp.tanh(0.7978845608 * (u + 0.044715 * u * u * u)))
    else:
        h = jax.nn.gelu(u)
    return h @ w_down


# --------------------------------------------------------------------------
# vocab-sharded, sequence-chunked softmax cross entropy
# --------------------------------------------------------------------------


@jax.custom_vjp
def _ct_cast_bf16(x):
    """Identity whose incoming cotangent is cast to bf16 — pins the whole
    backward residual chain to bf16 instead of the f32 the loss emits."""
    return x


def _ct_fwd(x):
    return x, None


def _ct_bwd(_, ct):
    return (ct.astype(jnp.bfloat16),)


_ct_cast_bf16.defvjp(_ct_fwd, _ct_bwd)


def chunked_xent(
    hidden: jnp.ndarray,      # (B, S, d)
    emb: jnp.ndarray,         # (V, d) — tied output embedding (vocab-sharded)
    labels: jnp.ndarray,      # (B, S) int32
    chunk: int = 256,
    z_loss: float = 1e-4,
) -> jnp.ndarray:
    """Mean next-token cross entropy without materializing (B, S, V).

    Scans over sequence chunks; within a chunk the (B, c, V) logits live
    only transiently and are vocab-sharded under pjit.  The small z-loss
    regularizes the softmax normalizer (production trick — keeps logits
    bounded in bf16 and gives XLA a second use of the lse so it fuses).
    """
    if tuning.get("grad_bf16") and hidden.dtype == jnp.bfloat16:
        hidden = _ct_cast_bf16(hidden)
    b, s, d = hidden.shape
    chunk = min(tuning.get("xent_chunk"), s)
    n = max(1, s // chunk)
    if n * chunk != s:
        chunk, n = s, 1
    emb = ctx.constrain(emb, ("model", None))
    hc = hidden.reshape(b, n, chunk, d).transpose(1, 0, 2, 3)
    lc = labels.reshape(b, n, chunk).transpose(1, 0, 2)

    @jax.checkpoint  # recompute chunk logits in backward: (B,c,V) never stored
    def body(carry, xs):
        h, l = xs
        logits = (h @ emb.astype(h.dtype).T).astype(jnp.float32)  # (B, c, V)
        lse = jax.nn.logsumexp(logits, axis=-1)
        true = jnp.take_along_axis(logits, l[..., None], axis=-1)[..., 0]
        nll = lse - true
        zl = z_loss * lse * lse
        return carry + jnp.sum(nll + zl), None

    total, _ = jax.lax.scan(body, jnp.float32(0.0), (hc, lc))
    return total / (b * s)
