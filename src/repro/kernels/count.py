"""Pallas TPU kernel: per-tuple intersection *counts* (no survivor recovery).

The suggestion workload (set-similarity join) only needs |A ∩ B|, never the
elements themselves.  That deletes everything expensive about the point-query
pipeline: no phase-1 filter pass, no survivor compaction, no capacity buffer,
no overflow re-run.  Each (probe group, candidate group) tuple reduces to one
scalar — the number of probe elements present in the aligned candidate group —
and the per-pair cardinality is the plain sum of those scalars over all G
tuples (each common element x lives in exactly one tuple: the one indexed by
its full-depth prefix, so summing over tuples counts it exactly once).

The kernel is the counting twin of ``group_intersect``: the same (8, ga, gb)
broadcast-equality tile, but reduced to an (8,) count instead of an (8, ga)
membership mask.  Output rows broadcast the count across the lane axis so the
store stays lane-aligned; callers read lane 0.

Padding follows the repo convention: probe rows pad with -1 (0xFFFFFFFF),
candidate rows pad with -2 so padded probes never match padded candidates.
Real universes exclude both sentinels (asserted during pre-processing).
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

LANES = 128
SUBLANES = 8
SENTINEL = -1  # 0xFFFFFFFF as int32 — python literal so kernels don't capture arrays


def pair_count_ref(a_vals: jnp.ndarray, b_vals: jnp.ndarray) -> jnp.ndarray:
    """Pure-jnp oracle: per-row count of real ``a`` elements present in ``b``.

    Args:
      a_vals: (S, ga) int32, sentinel-padded (-1) probe groups.
      b_vals: (S, gb) int32, aligned candidate groups.  Both accept leading
        batch axes: (..., S, ga) x (..., S, gb) -> (..., S).

    Returns:
      (..., S) int32 — exact |a ∩ b| per row when each row's real elements
      are duplicate-free (group rows of a preprocessed set always are).
    """
    eq = a_vals[..., :, None] == b_vals[..., None, :]
    hit = eq.any(axis=-1) & (a_vals != jnp.int32(SENTINEL))
    return hit.sum(axis=-1, dtype=jnp.int32)


def _count_kernel(a_ref, b_ref, out_ref):
    """a_ref: (8, gap) int32; b_ref: (8, gbp) int32; out_ref: (8, LANES) int32."""
    a = a_ref[...]
    b = b_ref[...]
    # int32 0/1 flags throughout: Mosaic does not reduce bool arrays
    eq = (a[:, :, None] == b[:, None, :]).astype(jnp.int32)  # (8, gap, gbp)
    hit = eq.max(axis=2)                          # any over b -> (8, gap)
    cnt = jnp.where(a != SENTINEL, hit, 0).sum(axis=1)  # (8,)
    out_ref[...] = jnp.broadcast_to(cnt[:, None], out_ref.shape)


def _pad_lanes(x: jnp.ndarray, fill) -> jnp.ndarray:
    s, g = x.shape
    gp = -(-g // LANES) * LANES
    sp = -(-s // SUBLANES) * SUBLANES
    return jnp.pad(x, ((0, sp - s), (0, gp - g)), constant_values=fill)


@functools.partial(jax.jit, static_argnames=("interpret",))
def pair_count_pallas(a_vals: jnp.ndarray, b_vals: jnp.ndarray, *,
                      interpret: bool) -> jnp.ndarray:
    """(S, ga) x (S, gb) sentinel-padded int32 -> (S,) int32 match counts.

    Leading batch axes fold into the row grid exactly as in
    ``group_match_pallas``: every row is an independent tuple, so
    (..., S, ga) x (..., S, gb) -> (..., S) by flattening onto sublanes.
    """
    if a_vals.ndim > 2:
        lead = a_vals.shape[:-1]
        ga = a_vals.shape[-1]
        gb = b_vals.shape[-1]
        flat = pair_count_pallas(
            a_vals.reshape(-1, ga), b_vals.reshape(-1, gb),
            interpret=interpret,
        )
        return flat.reshape(lead)
    s, _ = a_vals.shape
    a = _pad_lanes(a_vals.astype(jnp.int32), -1)
    # Pad B with a *different* sentinel (-2) so padded-A never matches padded-B;
    # real elements never equal either sentinel.
    b = _pad_lanes(b_vals.astype(jnp.int32), -2)
    sp, gap = a.shape
    _, gbp = b.shape
    out = pl.pallas_call(
        _count_kernel,
        grid=(sp // SUBLANES,),
        in_specs=[
            pl.BlockSpec((SUBLANES, gap), lambda i: (i, 0)),
            pl.BlockSpec((SUBLANES, gbp), lambda i: (i, 0)),
        ],
        out_specs=pl.BlockSpec((SUBLANES, LANES), lambda i: (i, 0)),
        out_shape=jax.ShapeDtypeStruct((sp, LANES), jnp.int32),
        interpret=interpret,
        # the kernel's name in the compiled program and the device trace
        name="pair_count",
    )(a, b)
    return out[:s, 0]
