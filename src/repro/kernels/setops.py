"""Batched dense sorted-set passes: union / difference / intersection.

The expression evaluator (``core/engine.py``) works on **dense value
buffers**: each leaf's ``(2^t, gmax)`` z-prefix group layout flattens to
one sorted uint32 row per query, and every DAG node is then a sort-merge
pass over its children's buffers.  This module holds those passes — pure
``jnp`` (XLA) implementations plus numpy references for unit tests.

Layout convention (shared with the intersection pipeline's packed
results): rows are **sorted uint32** with ``SENTINEL = 0xFFFFFFFF``
padding.  ``DeviceSet.from_host`` asserts real values stay below the
sentinel, and the int32 ``-1`` padding of device sets bitcasts to it, so
"sort ascending as uint32" puts padding last for free — that single
invariant is what makes every pass below a (concat →) sort → mask →
sort.

Why no hand-written Pallas here: unlike ``bitmap_filter`` /
``group_match`` (bit-twiddling the XLA fuser won't invent), these passes
are dominated by *sorting*, and ``sort_rows`` already lowers to the
backend's tuned sort (TPU sort HLO / CUB on GPU).  A Pallas bitonic
network would re-implement that slower.  The passes still run inside the
same jit'd, bucketed ``(B, …)`` executables as the kernels, so they
inherit the batching/compile-amortization story unchanged.

All passes are shape-static: callers pick the output width
(``min(capacity, natural width)``) and get back ``(buffer, count)`` —
``count`` is the TRUE result size, so ``count > width`` is the per-query
overflow signal that triggers the executor's single enlarged re-run.
"""
from __future__ import annotations

from typing import List, Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np

__all__ = [
    "SENTINEL", "sort_rows", "densify", "member_mask", "union_pass", "diff_pass",
    "intersect_pass", "densify_ref", "union_ref", "diff_ref",
    "intersect_ref",
]

# np scalar, not a jnp array: module import must stay trace-safe (a jnp
# constant created while some caller is tracing would leak that tracer
# into every later jit), and XLA folds the np scalar identically.
SENTINEL = np.uint32(0xFFFFFFFF)


def sort_rows(x: jnp.ndarray) -> jnp.ndarray:
    """Ascending sort along the last axis.

    Every device sort in this repo sorts one operand of plain integers, so
    stability cannot change its output; the unstable sort is the one the
    TPU compiler builds quickly (compile times for a TPU v5e are in
    ``PERF.md``; its run time on the chip is not measured).  ``jnp.sort``
    is stable."""
    return jax.lax.sort(x, dimension=x.ndim - 1, is_stable=False)


def densify(vals: jnp.ndarray) -> jnp.ndarray:
    """(B, 2^t, gmax) int32 device-set values (uint32 bitcast, -1 padded)
    -> (B, 2^t * gmax) sorted uint32 dense rows, sentinel-padded.  The
    -1 padding bitcasts to the sentinel, which sorts last."""
    u = jax.lax.bitcast_convert_type(vals, jnp.uint32)
    return sort_rows(u.reshape(u.shape[0], -1))


def member_mask(a: jnp.ndarray, b: jnp.ndarray) -> jnp.ndarray:
    """(B, La) needles x (B, Lb) sorted haystacks -> (B, La) bool: needle
    present in its row's haystack.  Sentinel needles are never members.
    Needles may be unsorted (only the haystack feeds searchsorted)."""
    idx = jax.vmap(jnp.searchsorted)(b, a)
    idx = jnp.clip(idx, 0, b.shape[1] - 1)
    hit = jnp.take_along_axis(b, idx, axis=1) == a
    return hit & (a != SENTINEL)


def union_pass(bufs: Sequence[jnp.ndarray], width: int
               ) -> Tuple[jnp.ndarray, jnp.ndarray]:
    """n-ary ∪ of sorted sentinel-padded rows -> (out (B, width) sorted,
    count (B,) int32 = true union size).  concat → sort → adjacent-dup
    mask → re-sort → slice; ``count > width`` means truncation."""
    cat = sort_rows(jnp.concatenate(list(bufs), axis=1))
    dup = jnp.concatenate(
        [jnp.zeros_like(cat[:, :1], dtype=bool), cat[:, 1:] == cat[:, :-1]],
        axis=1)
    uniq = jnp.where(dup, SENTINEL, cat)
    count = jnp.sum(uniq != SENTINEL, axis=1, dtype=jnp.int32)
    return sort_rows(uniq)[:, :width], count


def diff_pass(a: jnp.ndarray, b: jnp.ndarray, width: int
              ) -> Tuple[jnp.ndarray, jnp.ndarray]:
    """∖: drop ``a``'s members of ``b`` -> (out (B, width) sorted, count
    (B,) int32).  Both inputs sorted sentinel-padded rows."""
    out = jnp.where(member_mask(a, b), SENTINEL, a)
    count = jnp.sum(out != SENTINEL, axis=1, dtype=jnp.int32)
    return sort_rows(out)[:, :width], count


def intersect_pass(bufs: Sequence[jnp.ndarray], width: int
                   ) -> Tuple[jnp.ndarray, jnp.ndarray]:
    """n-ary ∩ -> (out (B, width) sorted, count (B,) int32).  Folds
    membership onto the first (canonically smallest) buffer."""
    acc = bufs[0]
    for b in bufs[1:]:
        acc = jnp.where(member_mask(acc, b), acc, SENTINEL)
    count = jnp.sum(acc != SENTINEL, axis=1, dtype=jnp.int32)
    return sort_rows(acc)[:, :width], count


# ---------------------------------------------------------------------------
# numpy references (unit-test oracles for the passes themselves)
# ---------------------------------------------------------------------------

_SENT_NP = np.uint32(0xFFFFFFFF)


def _pad_rows(rows: List[np.ndarray], width: int) -> np.ndarray:
    out = np.full((len(rows), width), _SENT_NP, dtype=np.uint32)
    for i, r in enumerate(rows):
        out[i, :min(len(r), width)] = r[:width]
    return out


def densify_ref(vals: np.ndarray) -> np.ndarray:
    u = vals.astype(np.int64).reshape(vals.shape[0], -1)
    u = np.where(u < 0, int(_SENT_NP), u).astype(np.uint32)
    return np.sort(u, axis=1)


def union_ref(bufs: Sequence[np.ndarray], width: int
              ) -> Tuple[np.ndarray, np.ndarray]:
    rows, counts = [], []
    for i in range(bufs[0].shape[0]):
        vals = np.concatenate([b[i][b[i] != _SENT_NP] for b in bufs])
        u = np.unique(vals)
        rows.append(u)
        counts.append(len(u))
    return _pad_rows(rows, width), np.asarray(counts, dtype=np.int32)


def diff_ref(a: np.ndarray, b: np.ndarray, width: int
             ) -> Tuple[np.ndarray, np.ndarray]:
    rows, counts = [], []
    for i in range(a.shape[0]):
        d = np.setdiff1d(a[i][a[i] != _SENT_NP], b[i][b[i] != _SENT_NP])
        rows.append(d)
        counts.append(len(d))
    return _pad_rows(rows, width), np.asarray(counts, dtype=np.int32)


def intersect_ref(bufs: Sequence[np.ndarray], width: int
                  ) -> Tuple[np.ndarray, np.ndarray]:
    rows, counts = [], []
    for i in range(bufs[0].shape[0]):
        out = bufs[0][i][bufs[0][i] != _SENT_NP]
        for b in bufs[1:]:
            out = np.intersect1d(out, b[i][b[i] != _SENT_NP])
        rows.append(out)
        counts.append(len(out))
    return _pad_rows(rows, width), np.asarray(counts, dtype=np.int32)
