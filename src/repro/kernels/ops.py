"""Public jit'd wrappers over the Pallas kernels (with pure-jnp fallback).

``use_pallas`` selects the execution path:
  * "auto"   — Pallas compiled on TPU, the pure-jnp reference elsewhere.
  * True     — always Pallas (interpret on non-TPU backends).
  * False    — pure-jnp reference (ref.py) — same semantics, used for
               oracle checks and for CPU-speed benchmarks where the python
               interpret loop would dominate.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

from . import ref
from .bitmap_filter import bitmap_filter_pallas
from .count import pair_count_pallas, pair_count_ref
from .group_intersect import group_match_pallas


def _on_tpu() -> bool:
    return jax.default_backend() == "tpu"


def bitmap_filter(images: jnp.ndarray, use_pallas="auto") -> jnp.ndarray:
    """(k, G, m, W) stacked images -> (G,) survivor mask (bool).

    A leading batch axis — (B, k, G, m, W) -> (B, G) — runs B queries of
    identical static shape in one call (the exec subsystem's bucketed
    batches); the Pallas path folds the batch into the kernel grid.
    """
    if use_pallas == "auto":
        use_pallas = _on_tpu()
    if use_pallas:
        return bitmap_filter_pallas(images, interpret=not _on_tpu())
    return ref.bitmap_filter_ref(images)


def group_match(a_vals: jnp.ndarray, b_vals: jnp.ndarray,
                use_pallas="auto") -> jnp.ndarray:
    """(S, ga), (S, gb) sentinel-padded -> (S, ga) membership mask (bool).

    Leading batch axis supported: (B, S, ga) x (B, S, gb) -> (B, S, ga);
    the Pallas path flattens it onto the row grid.
    """
    if use_pallas == "auto":
        use_pallas = _on_tpu()
    if use_pallas:
        return group_match_pallas(a_vals, b_vals, interpret=not _on_tpu())
    return ref.group_match_ref(a_vals.astype(jnp.int32), b_vals.astype(jnp.int32))


def pair_count(a_vals: jnp.ndarray, b_vals: jnp.ndarray,
               use_pallas="auto") -> jnp.ndarray:
    """(S, ga), (S, gb) sentinel-padded -> (S,) int32 match counts.

    The count-only twin of :func:`group_match` — the same broadcast-equality
    test, reduced to one scalar per row, so the suggestion path never
    materializes survivor buffers.  Leading batch axes supported:
    (..., S, ga) x (..., S, gb) -> (..., S).
    """
    if use_pallas == "auto":
        use_pallas = _on_tpu()
    if use_pallas:
        return pair_count_pallas(a_vals, b_vals, interpret=not _on_tpu())
    return pair_count_ref(a_vals.astype(jnp.int32), b_vals.astype(jnp.int32))


def vocab_mask_and(masks: jnp.ndarray, use_pallas="auto") -> jnp.ndarray:
    """Constrained-decoding mask intersection: (k, V//32) uint32 packed
    allowed-token bitmaps -> (V//32,) packed AND.

    This is Algorithm 2 line 1 at vocabulary scale — one group of size V,
    word representation of width V bits.  The AND itself is a trivial
    elementwise reduce; it reuses the same packed-lane layout as the filter
    kernel so serving code has a single bitmap convention.
    """
    out = masks[0]
    for i in range(1, masks.shape[0]):
        out = out & masks[i]
    return out


def unpack_vocab_mask(packed: jnp.ndarray, vocab: int) -> jnp.ndarray:
    """(V//32,) packed uint32 -> (V,) bool allowed mask (lowest bit first)."""
    bits = (packed[:, None] >> jnp.arange(32, dtype=jnp.uint32)) & jnp.uint32(1)
    return bits.reshape(-1)[:vocab].astype(bool)


def pack_vocab_mask(allowed: jnp.ndarray) -> jnp.ndarray:
    """(V,) bool -> (ceil(V/32),) packed uint32."""
    v = allowed.shape[0]
    vp = -(-v // 32) * 32
    a = jnp.pad(allowed.astype(jnp.uint32), (0, vp - v)).reshape(-1, 32)
    return (a << jnp.arange(32, dtype=jnp.uint32)).sum(axis=1, dtype=jnp.uint32)
