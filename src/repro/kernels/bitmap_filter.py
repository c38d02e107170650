"""Pallas TPU kernel: batched word-representation AND filter (Alg. 5 line 3).

This is the perf-critical hot spot of the paper's online stage: for every
group tuple, AND the k sets' m hash images and test each of the m results
for non-emptiness.  Arithmetic intensity is ~0.25 ops/byte — firmly
memory-bound — so the kernel's job is purely to stream HBM at line rate
through VMEM with hardware-aligned tiles and no layout changes.

TPU-native layout: **groups live on the 128 lanes**, the m*W packed bitmap
words live on sublanes.  The wrapper reshapes the logical (k, G, m, W)
images to (k, F, G) with F = m*Wp (Wp = W padded so F is a multiple of 8,
the int32 sublane tile).  Each grid step processes one (F, 128) tile per
set: k-way AND on the VPU, OR-reduce over each image's Wp words, non-zero
test, AND-reduce over the m images — emitting 128 survivor flags per step.

Multi-query batching (the exec subsystem's bucketed execution) folds the
batch straight into the grid: a (B, k, G, m, W) input runs a (B, G/128)
grid where grid step (b, i) streams query b's i-th lane tile.  Queries in
a bucket share one static shape, so the whole bucket is a single
pallas_call — no vmap wrapper, no per-query dispatch.

VMEM working set per step: (k+1) * F * 128 * 4 bytes — for k=4, m=2, W=8
that is 40 KiB, far under the ~16 MiB VMEM budget, leaving headroom for
the double-buffered pipeline pallas_call builds automatically.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

LANES = 128
SUBLANES = 8


def _filter_kernel(imgs_ref, out_ref, *, k: int, m: int, wp: int):
    """imgs_ref: (1, k, F, 128) int32 block; out_ref: (1, 8, 128) int32 block."""
    h = imgs_ref[0, 0]
    for i in range(1, k):                      # k is tiny & static: unroll
        h = h & imgs_ref[0, i]                 # (F, 128) VPU AND
    hw = h.reshape(m, wp, LANES)               # split images from words
    # int32 0/1 flags: Mosaic does not reduce bool arrays
    nonzero = (hw != 0).astype(jnp.int32).max(axis=1)  # OR over words: (m, 128)
    passed = nonzero.min(axis=0)               # AND over images -> (128,)
    out_ref[...] = jnp.broadcast_to(passed, (1, SUBLANES, LANES))


def _pack(images: jnp.ndarray):
    """(B, k, G, m, W) -> (B, k, F, Gp) int32 with F = m*Wp, zero padding."""
    b, k, g, m, w = images.shape
    wp = w
    while (m * wp) % SUBLANES:
        wp += 1
    gp = -(-g // LANES) * LANES
    x = (jax.lax.bitcast_convert_type(images, jnp.int32)
         if images.dtype == jnp.uint32 else images.astype(jnp.int32))
    x = jnp.pad(x, ((0, 0), (0, 0), (0, gp - g), (0, 0), (0, wp - w)))
    x = x.reshape(b, k, gp, m * wp).transpose(0, 1, 3, 2)  # (B, k, F, Gp)
    return x, wp, gp


@functools.partial(jax.jit, static_argnames=("interpret",))
def bitmap_filter_pallas(images: jnp.ndarray, *, interpret: bool) -> jnp.ndarray:
    """Survivor mask for (k, G, m, W) or (B, k, G, m, W) group-tuple images.

    Returns (G,) / (B, G) bool — see kernels.ref.bitmap_filter_ref for
    semantics.  A leading batch axis becomes the leading grid axis.
    """
    batched = images.ndim == 5
    if not batched:
        images = images[None]
    b, k, g, m, w = images.shape
    packed, wp, gp = _pack(images)
    f = m * wp
    kern = functools.partial(_filter_kernel, k=k, m=m, wp=wp)
    out = pl.pallas_call(
        kern,
        grid=(b, gp // LANES),
        in_specs=[
            pl.BlockSpec((1, k, f, LANES), lambda bi, i: (bi, 0, 0, i)),
        ],
        out_specs=pl.BlockSpec((1, SUBLANES, LANES), lambda bi, i: (bi, 0, i)),
        out_shape=jax.ShapeDtypeStruct((b, SUBLANES, gp), jnp.int32),
        interpret=interpret,
        # the kernel's name in the compiled program and the device trace
        name="bitmap_filter",
    )(packed)
    mask = out[:, 0, :g].astype(bool)
    return mask if batched else mask[0]
