"""Pallas TPU kernel: all-pairs match of survivor small groups.

The paper recovers the intersection of a surviving group pair by merging
h^{-1} linked lists — serial, branchy, perfect for a CPU, degenerate on a
TPU.  The TPU-native replacement: for each surviving tuple, compare every
element of group A against every element of group B in one (ga x gb)
broadcast-equality tile.  With the paper's group size ~sqrt(w) <= 32 the
tile is tiny, branch-free, and lane-parallel; 8 tuples are processed per
grid step so the compare tile is (8, ga, gb) — at ga=gb=128 that is 512 KiB
of bool in VMEM, still comfortably inside budget.

Padding uses the sentinel 0xFFFFFFFF (= -1 as int32); real universes exclude
it (asserted during pre-processing), so masks are implicit in the values.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

LANES = 128
SUBLANES = 8
SENTINEL = -1  # 0xFFFFFFFF as int32 — python literal so kernels don't capture arrays


def _match_kernel(a_ref, b_ref, out_ref):
    """a_ref: (8, gap) int32; b_ref: (8, gbp) int32; out_ref: (8, gap) int32."""
    a = a_ref[...]
    b = b_ref[...]
    # int32 0/1 flags throughout: Mosaic does not reduce bool arrays
    eq = (a[:, :, None] == b[:, None, :]).astype(jnp.int32)  # (8, gap, gbp)
    hit = eq.max(axis=2)                          # any over b -> (8, gap)
    out_ref[...] = jnp.where(a != SENTINEL, hit, 0)


def _pad_lanes(x: jnp.ndarray, fill) -> jnp.ndarray:
    s, g = x.shape
    gp = -(-g // LANES) * LANES
    sp = -(-s // SUBLANES) * SUBLANES
    return jnp.pad(x, ((0, sp - s), (0, gp - g)), constant_values=fill)


@functools.partial(jax.jit, static_argnames=("interpret",))
def group_match_pallas(a_vals: jnp.ndarray, b_vals: jnp.ndarray, *,
                       interpret: bool) -> jnp.ndarray:
    """(S, ga) x (S, gb) sentinel-padded int32 -> (S, ga) bool membership.

    A leading batch axis ((B, S, ga) x (B, S, gb) -> (B, S, ga)) folds into
    the row grid: every row is an independent tuple regardless of which
    query it came from, so the batch flattens onto the sublane axis and the
    kernel is unchanged.
    """
    if a_vals.ndim == 3:
        bsz, s, ga = a_vals.shape
        gb = b_vals.shape[-1]
        flat = group_match_pallas(
            a_vals.reshape(bsz * s, ga), b_vals.reshape(bsz * s, gb),
            interpret=interpret,
        )
        return flat.reshape(bsz, s, ga)
    s, ga = a_vals.shape
    _, gb = b_vals.shape
    a = _pad_lanes(a_vals.astype(jnp.int32), -1)
    # Pad B with a *different* sentinel (-2) so padded-A never matches padded-B;
    # real elements never equal either sentinel.
    b = _pad_lanes(b_vals.astype(jnp.int32), -2)
    sp, gap = a.shape
    _, gbp = b.shape
    out = pl.pallas_call(
        _match_kernel,
        grid=(sp // SUBLANES,),
        in_specs=[
            pl.BlockSpec((SUBLANES, gap), lambda i: (i, 0)),
            pl.BlockSpec((SUBLANES, gbp), lambda i: (i, 0)),
        ],
        out_specs=pl.BlockSpec((SUBLANES, gap), lambda i: (i, 0)),
        out_shape=jax.ShapeDtypeStruct((sp, gap), jnp.int32),
        interpret=interpret,
        # the kernel's name in the compiled program and the device trace
        name="group_match",
    )(a, b)
    return out[:s, :ga].astype(bool)
