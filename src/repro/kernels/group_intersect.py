"""Pallas TPU kernel: all-pairs match of survivor small groups.

The paper recovers the intersection of a surviving group pair by merging
h^{-1} linked lists — serial, branchy, perfect for a CPU, degenerate on a
TPU.  The TPU-native replacement: for each surviving tuple, compare every
element of group A against every element of group B, branch-free.

Tuples (rows) sit on the 128 lanes and a group's elements on the sublanes,
so each compare is a full vreg of independent tuples: the wrapper lays ``a``
out as ``(S/128, ga_p, 128)`` and ``b`` as ``(S/128, gb, 128)`` (``ga_p`` is
``ga`` rounded up to 8 sublanes), and for every ``j < gb`` the kernel
broadcasts element ``j`` of each ``b`` row across the sublanes and folds
``a == b[j]`` into a running hit mask.  There is no cross-lane reduction and
no padding of a group to 128 lanes, so at the paper's group size
~sqrt(w) <= 32 nearly every compare is real work.  Each grid step takes ``R``
rows, ``R`` a multiple of 128 derived from ``S`` and the group widths.

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
MAX_ROWS = 2048              # rows per grid step, at most
VMEM_BUDGET = 8 * 2**20      # bytes of double-buffered blocks per grid step


def _match_kernel(a_ref, b_ref, out_ref):
    """a_ref: (C, gap, 128); b_ref: (C, gb, 128); out_ref: (C, gap, 128)
    int32 — C chunks of 128 rows, one row per lane."""
    gb = b_ref.shape[1]

    def chunk(c, carry):
        a = a_ref[c]                               # (gap, 128)
        b = b_ref[c]                               # (gb, 128)
        # int32 0/1 flags throughout: Mosaic does not reduce bool arrays
        hit = jnp.zeros_like(a)
        for j in range(gb):
            hit = jnp.where(a == b[j:j + 1, :], 1, hit)
        out_ref[c] = jnp.where(a != SENTINEL, hit, 0)
        return carry

    jax.lax.fori_loop(0, a_ref.shape[0], chunk, 0)


def _rows_per_step(s: int, gap: int, gb: int) -> int:
    """Rows per grid step: a multiple of 128, at most ``MAX_ROWS`` and no more
    than ``S`` needs, with the double-buffered a, b and out blocks inside
    ``VMEM_BUDGET``."""
    gbp = -(-gb // SUBLANES) * SUBLANES
    fit = VMEM_BUDGET // (2 * 4 * (2 * gap + gbp))
    return max(LANES, min(-(-s // LANES) * LANES, MAX_ROWS,
                          fit // LANES * LANES))


def _rows_on_lanes(x: jnp.ndarray, sp: int, gp: int, fill) -> jnp.ndarray:
    """(S, g) -> (sp/128, gp, 128): padded with ``fill``, rows on lanes."""
    s, g = x.shape
    x = jnp.pad(x, ((0, sp - s), (0, gp - g)), constant_values=fill)
    return x.reshape(sp // LANES, LANES, gp).transpose(0, 2, 1)


@functools.partial(jax.jit, static_argnames=("interpret",))
def group_match_pallas(a_vals: jnp.ndarray, b_vals: jnp.ndarray, *,
                       interpret: bool) -> jnp.ndarray:
    """(S, ga) x (S, gb) sentinel-padded int32 -> (S, ga) bool membership.

    A leading batch axis ((B, S, ga) x (B, S, gb) -> (B, S, ga)) folds into
    the row grid: every row is an independent tuple regardless of which
    query it came from, so the batch flattens onto the lane axis and the
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
    gap = -(-ga // SUBLANES) * SUBLANES
    rows = _rows_per_step(s, gap, gb)
    sp = -(-s // rows) * rows
    a = _rows_on_lanes(a_vals.astype(jnp.int32), sp, gap, -1)
    # Pad B with a *different* sentinel (-2) so padded-A never matches padded-B;
    # real elements never equal either sentinel.
    b = _rows_on_lanes(b_vals.astype(jnp.int32), sp, gb, -2)
    chunks = rows // LANES
    out = pl.pallas_call(
        _match_kernel,
        grid=(sp // rows,),
        in_specs=[
            pl.BlockSpec((chunks, gap, LANES), lambda i: (i, 0, 0)),
            pl.BlockSpec((chunks, gb, LANES), lambda i: (i, 0, 0)),
        ],
        out_specs=pl.BlockSpec((chunks, gap, LANES), lambda i: (i, 0, 0)),
        out_shape=jax.ShapeDtypeStruct((sp // LANES, gap, LANES), jnp.int32),
        interpret=interpret,
        # the kernel's name in the compiled program and the device trace
        name="group_match",
    )(a, b)
    return out.transpose(0, 2, 1).reshape(sp, gap)[:s, :ga].astype(bool)
