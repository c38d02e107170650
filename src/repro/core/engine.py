"""Device-resident batched intersection engine (the paper's system on TPU).

Module map (the device path, bottom-up):

  kernels/            bitmap_filter / group_match Pallas kernels + jnp oracles;
                      both accept a leading batch axis folded into the grid.
  core/engine.py      this file — DeviceSet mirrors, the jit'd batched
                      two-phase pipeline (``_intersect_k_batch``), the
                      bucket executor entry point ``intersect_device_batch``,
                      and the z-sharded variant ``intersect_sharded``.
  exec/plan.py        query normalization (term dedup, sort by (t, n),
                      hashbin-vs-device policy) into shape-keyed QueryPlans.
  exec/batch.py       groups QueryPlans by shape signature, stacks DeviceSet
                      rows, and drives ``intersect_device_batch`` — one jit
                      execution per bucket plus rare overflow re-runs.
  serve/search.py     SearchEngine: plan -> bucket -> execute -> scatter.

Pre-processed sets (``partition.PrefixIndex``) are mirrored to the device as
dense arrays; intersections run as two fused phases:

  phase 1 (filter):  gather prefix-aligned images, k-way AND, m-way test
                     (kernels.ops.bitmap_filter — the paper's Alg. 5 line 3)
  phase 2 (recover): compact survivors to a static capacity, all-pairs match
                     of the raw groups (kernels.ops.group_match)

Static shapes everywhere: the survivor set is compacted into a fixed
``capacity`` buffer (per-query overflow flags returned; the executor re-runs
the rare overflowing subset once at full capacity).  This preserves the
paper's work-saving — the expensive phase 2 runs on ``capacity ≈
E[survivors]`` group tuples instead of all ``G`` — inside an XLA-compatible
regime.

Multi-query batching: the online stage is embarrassingly parallel across
queries, and real query logs concentrate on a handful of shape signatures
``(k, ts, gmaxes, capacity)`` (the paper's workload model: 68% 2-word, 23%
3-word queries).  ``_intersect_k_batch`` therefore takes ``(B, …)`` stacked
arrays and runs a whole same-signature bucket in ONE jit execution; the
single-query ``intersect_device`` is just a batch of one through the same
pipeline, so both paths share one compile cache.

Distribution: :func:`intersect_sharded_batch` shard_maps the z-prefix space
over a 1-D device mesh.  Because every set is partitioned by the *same*
permutation ``g`` (Theorem 3.7's alignment), equal z-range blocks of every
set land on the same shard and both phases are entirely local; only the
per-shard result buffers are concatenated at the end.  The paper's
partitioning function doubles as the sharding function.  The sharded path
is the same ``(B, …)`` bucketed pipeline as :func:`intersect_device_batch`
— sort-compaction survivor selection, packed single-transfer results,
per-(query, shard) overflow flags with ONE enlarged re-run pass — so
sharded results are bit-identical to the unsharded and host paths.
:func:`intersect_sharded` is a batch of one through it.

2-D distribution: :func:`intersect_mesh2d_batch` generalizes the 1-D case
to a ``Mesh(("data", "shard"))`` built by :func:`make_mesh2d` — the batch
axis splits over ``data`` (each replica row holds a full copy of the
posting mirrors and processes ``B / replicas`` queries) while the z-prefix
space splits over ``shard`` within every replica, exactly as in the 1-D
path.  The data axis is driven host-side: each row's batch slice is ONE
async dispatch of the row-local pipeline (the 1-D z-sharded shard_map over
the row's submesh, or the plain single-device pipeline when ``shards ==
1``), and all rows are collected at a single point — so rows overlap in
flight and no collective ever crosses the data axis.  (A single 2-D
shard_map was measured 3-10x slower here: GSPMD materializes the in-jit
batch stack replicated on every row before slicing it.)  Both phases stay
communication-free; the same per-(query, shard) overflow flags drive the
same single enlarged re-run, so 2-D results are bit-identical to the 1-D,
unsharded, and host paths.  The topology layer (``exec/topology.py``) owns
mesh construction, replica placement, and the per-replica load balancer
that spreads single-device buckets across replica rows.

Asynchronous dispatch: every pipeline is split into a non-blocking
``dispatch_*_batch`` half (jit call issued; JAX async dispatch returns
device arrays that are futures) and a blocking :meth:`PendingBatch.collect`
half (deferred ``jax.device_get`` + overflow re-runs + host
post-processing), with ``intersect_*_batch`` kept as the synchronous
composition of the two.  The exec layer (``exec/batch.py``) builds its
:class:`InFlightBucket` window on these halves so *independent buckets*
overlap on the device — the serving-layer throughput win the per-bucket
row overlap above cannot provide.
"""
from __future__ import annotations

import dataclasses
import functools
import threading
import time
from typing import (Callable, Dict, List, NamedTuple, Optional, Sequence,
                    Tuple)

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from ..kernels import ops, setops
from .partition import PrefixIndex

__all__ = [
    "DeviceSet",
    "ReplicatedDeviceSet",
    "DATA_AXIS",
    "SHARD_AXIS",
    "SHARD_MIN_G",
    "default_capacity",
    "default_capacity_per_shard",
    "PassRecord",
    "PendingBatch",
    "dispatch_device_batch",
    "dispatch_mesh2d_batch",
    "dispatch_sharded_batch",
    "intersect_device",
    "intersect_device_batch",
    "intersect_mesh2d_batch",
    "intersect_sharded",
    "intersect_sharded_batch",
    "default_k_tier",
    "dispatch_count_batch",
    "dispatch_count_sharded_batch",
    "dispatch_count_mesh2d_batch",
    "intersect_count_batch",
    "intersect_count_sharded_batch",
    "intersect_count_mesh2d_batch",
    "dispatch_expr_batch",
    "dispatch_expr_sharded_batch",
    "dispatch_expr_mesh2d_batch",
    "intersect_expr_batch",
    "intersect_expr_sharded_batch",
    "intersect_expr_mesh2d_batch",
    "expr_total_width",
    "default_expr_capacity",
    "default_expr_capacity_per_shard",
    "make_mesh2d",
    "make_shard_mesh",
    "bucket_hlo_text",
    "pow2_tiers",
    "set_sort_key",
    "warm_executables",
    "warm_from_plans",
    "clear_exec_jit_cache",
    "BatchedEngine",
    "EXEC_COUNTERS",
    "ExecCounters",
    "reset_exec_counters",
]

SHARD_AXIS = "shard"  # canonical name of the z-sharding mesh axis
DATA_AXIS = "data"    # canonical name of the data-parallel (replica) axis

# Default sharding threshold: route a query z-sharded only when its largest
# set has at least this many group tuples.  2^12 groups ≈ a 65k-element set
# at w=256 — below that, a single device finishes a bucket faster than the
# mesh can dispatch it.  (Single source of truth; exec.plan re-exports it.)
SHARD_MIN_G = 4096

class ExecCounters(dict):
    """Telemetry for the batched device path and the serving front-end.

    A plain ``dict`` subclass so existing ``EXEC_COUNTERS["key"]`` reads and
    writes keep working; ``reset()`` zeroes every counter (test setup calls
    it autouse so counter-asserting tests are order-independent).

    Keys:

    - ``batch_calls``     jit *executions* of the bucketed pipeline (what
      per-query dispatch would make O(#queries) and bucketing makes
      O(#signatures)).
    - ``batch_traces``    actual retraces (compiles) of the pipeline — one
      per distinct ``(ShapeSig, B-tier)`` pair over the process lifetime.
    - ``rerun_calls``     overflow re-run passes (survivors > capacity).
    - ``sharded_calls`` / ``sharded_traces`` / ``sharded_rerun_calls`` —
      the same three for the z-sharded pipeline
      (:func:`intersect_sharded_batch`); kept separate so a mixed workload
      reports single-device and mesh executions independently.
    - ``mesh2d_calls`` / ``mesh2d_traces`` / ``mesh2d_rerun_calls`` — the
      same three for the 2-D data x shard pipeline
      (:func:`intersect_mesh2d_batch`); one ``mesh2d_calls`` per bucket
      *pass* (each pass issues ``replicas`` concurrent row executions,
      counted separately in ``mesh2d_row_dispatches``).
    - ``replica_dispatches`` — single-device buckets routed to a replica
      row by the topology's load balancer (``exec/topology.py``).
    - ``inflight_dispatches`` — buckets dispatched asynchronously through
      ``exec/batch.py::dispatch_bucket`` (one per :class:`InFlightBucket`
      handle, whether or not anything overlapped).
    - ``inflight_collects`` — in-flight buckets torn down (first collect
      completion OR failure; one-shot per bucket).  After any drain,
      ``inflight_dispatches == inflight_collects`` — the
      no-lost-bucket invariant the loadgen soak test asserts.
    - ``collect_us`` — cumulative microseconds spent in the blocking
      *collect* phase (``jax.device_get`` wait + overflow re-runs + host
      post-processing); dispatch-to-collect overlap shows up as wall time
      that is NOT in this counter.
    - ``overlap_high_water`` — the maximum number of buckets that were
      simultaneously in flight (dispatched, not yet collected) since the
      last reset: ``>= 2`` is the signature of real dispatch/collect
      overlap, ``<= 1`` means execution was effectively synchronous.
    - ``warm_executions`` pipeline executions issued by compile warming
      (:func:`warm_executables`) at index-build time.
    - ``result_cache_hits`` / ``result_cache_misses`` — lookups in the
      normalized-plan result cache (``exec/cache.py``).
    - ``tier_flushes`` / ``deadline_flushes`` — admission-queue bucket
      flushes by cause: reached the full power-of-two tier vs. the oldest
      query's deadline budget expired (``serve/admission.py``).
    - ``tickets_resolved`` / ``queue_wait_us`` / ``deadline_violations`` —
      per-ticket wait telemetry stamped at resolution
      (``serve/admission.py::Ticket``): tickets resolved (value or error),
      cumulative queue wait in integer microseconds, and resolutions whose
      wait exceeded the ticket's own deadline budget (>0.5 us past it —
      the virtual-clock float-epsilon used by the admission benchmark).
      These are what the SLO-burn load harness reads.
    - ``flusher_wakeups`` — background flusher thread wake-ups
      (``serve/search.py::AsyncSearchEngine.start``): each sleep that ended
      (deadline due, submit wake, or idle timeout) and led to a pump check.
    - ``adaptive_promotions`` / ``adaptive_demotions`` — capacity-tier
      increases / decreases learned by ``exec/adaptive.py::CapacityModel``
      (demotions happen when time-decayed survivor windows show the
      workload drifted down).  ``adaptive_overflow_saved`` — executions
      where the learned tier absorbed survivors that would have overflowed
      the static G/4 rule (i.e. re-runs the model eliminated).
    - ``expr_calls`` / ``expr_traces`` / ``expr_rerun_calls`` — the same
      call/compile/overflow-re-run triple for the boolean **expression**
      pipeline (``_eval_expr_batch`` and its sharded / 2-D twins — all
      three report under one family, like the flat pipeline's per-path
      split but coarser, since expression traffic is one workload).
    - ``subexpr_cache_hits`` / ``subexpr_cache_misses`` — lookups of
      canonicalized *sub*expression entries in the result cache
      (``exec/cache.py::ResultCache.get_sub``); ``subexpr_cache_stores``
      — sub-buffers stored after expression bucket execution;
      ``subexpr_host_merges`` — expression queries answered entirely
      host-side by merging cached subexpression values (zero device
      work).
    - ``count_calls`` / ``count_traces`` — jit executions / retraces of
      the count-only suggestion pipeline (``_intersect_count_batch`` and
      its z-sharded / 2-D twins; one family — there is no overflow re-run
      to count, the count path has no survivor buffer at all).
    - ``suggest_prefilter_in`` / ``suggest_prefilter_kept`` — corpus
      candidates considered / kept by the hashbin candidate pre-filter
      (``exec/candidates.py::CandidateIndex``); the ratio is the
      pre-filter's device-work saving on the suggest workload.
    - ``dispatch_failures`` — buckets whose dispatch or collect raised
      (the balancer releases the weight; this counter is the telemetry
      trace the release alone never left).  Mirrored as a typed counter
      in ``repro.obs``.

    Counters are process-global.  Writes and snapshots serialize on one
    internal lock: plain ``EXEC_COUNTERS["key"] += n`` sites keep working
    (each read and write is individually consistent; the read-modify-write
    itself can still lose a concurrent bump — last-write-wins noise, as
    ever), while :meth:`bump` / :meth:`bump_many` do the whole
    read-modify-write under the lock and :meth:`snapshot` copies every key
    under the same lock.  The contract: keys that must stay mutually
    consistent across a concurrent snapshot (e.g. the
    ``tickets_resolved`` / ``queue_wait_us`` pair) are updated through one
    ``bump_many`` call, and readers use ``snapshot()`` instead of key-at-
    a-time reads — a snapshot can then never observe one of the pair
    without the other.
    """

    _KEYS = (
        "batch_calls", "batch_traces", "rerun_calls",
        "sharded_calls", "sharded_traces", "sharded_rerun_calls",
        "mesh2d_calls", "mesh2d_traces", "mesh2d_rerun_calls",
        "mesh2d_row_dispatches", "replica_dispatches",
        "inflight_dispatches", "inflight_collects",
        "collect_us", "overlap_high_water",
        "warm_executions",
        "result_cache_hits", "result_cache_misses",
        "tier_flushes", "deadline_flushes",
        "tickets_resolved", "queue_wait_us", "deadline_violations",
        "flusher_wakeups",
        "adaptive_promotions", "adaptive_demotions",
        "adaptive_overflow_saved",
        "expr_calls", "expr_traces", "expr_rerun_calls",
        "subexpr_cache_hits", "subexpr_cache_misses",
        "subexpr_cache_stores", "subexpr_host_merges",
        "count_calls", "count_traces",
        "suggest_prefilter_in", "suggest_prefilter_kept",
        "dispatch_failures",
    )

    def __init__(self):
        super().__init__({k: 0 for k in self._KEYS})
        # Not reentrant: locked methods below write via dict.__setitem__
        # directly so they never recurse into the locking override.
        self._lock = threading.Lock()

    def __setitem__(self, key, value) -> None:
        with self._lock:
            dict.__setitem__(self, key, value)

    def bump(self, key: str, n: int = 1) -> None:
        """Atomic read-modify-write increment of one counter."""
        with self._lock:
            dict.__setitem__(self, key, dict.__getitem__(self, key) + n)

    def bump_many(self, deltas: dict) -> None:
        """Atomically apply several increments — no snapshot can observe
        a strict subset of them."""
        with self._lock:
            for key, n in deltas.items():
                dict.__setitem__(self, key, dict.__getitem__(self, key) + n)

    def snapshot(self) -> dict:
        """A consistent copy of every counter, taken under the write
        lock (the fix for key-at-a-time reads tearing mid-flush)."""
        with self._lock:
            return {k: dict.__getitem__(self, k) for k in self._KEYS}

    def reset(self) -> None:
        with self._lock:
            for key in self._KEYS:
                dict.__setitem__(self, key, 0)


EXEC_COUNTERS = ExecCounters()


def reset_exec_counters() -> None:
    """Back-compat alias for :meth:`ExecCounters.reset`."""
    EXEC_COUNTERS.reset()


@dataclasses.dataclass(frozen=True)
class DeviceSet:
    """Device mirror of a PrefixIndex (sentinel-padded; mask implicit).

    ``gmax`` is quantized up to a power of two on mirroring: the exact
    per-set max group size is what it is on the host, but on the device it
    is a *static shape* — leaving it exact would give nearly every set its
    own shape signature and defeat bucketed batching.  Power-of-two tiers
    cost at most 2x padding on the tiny phase-2 tiles and collapse the
    signature space to a handful of buckets.
    """

    t: int
    gmax: int
    m: int
    w: int
    n: int
    vals: jnp.ndarray     # (2^t, gmax) int32 (original values; -1 padding)
    images: jnp.ndarray   # (2^t, m, W) uint32

    @classmethod
    def from_host(cls, idx: PrefixIndex) -> "DeviceSet":
        assert int(idx.values.max(initial=0)) < 0xFFFFFFFF, "sentinel collision"
        gmax = gmax_tier(idx.gmax)
        padded = np.pad(
            idx.padded_vals, ((0, 0), (0, gmax - idx.gmax)),
            constant_values=np.uint32(0xFFFFFFFF),
        )
        # reinterpret on the host: one transfer per array, no device op
        return cls(
            t=idx.t, gmax=gmax, m=idx.family.m, w=idx.w, n=idx.n,
            vals=jnp.asarray(padded.view(np.int32)),
            images=jnp.asarray(idx.images),
        )

    def shardable(self, n_shards: int) -> bool:
        """True when the z axis splits evenly over ``n_shards`` — the
        Theorem 3.7 alignment condition (every shard holds at least one
        whole z-group of this set)."""
        return n_shards >= 1 and (1 << self.t) % n_shards == 0

    def shard(self, mesh: Mesh, axis: str = SHARD_AXIS) -> "DeviceSet":
        """Z-sharded mirror: both arrays placed with their leading (z) axis
        partitioned over ``mesh[axis]``.  Built once at index time so the
        sharded pipeline never pays a per-call reshard; the unsharded
        mirror stays as-is for single-device buckets.  The 2-D topology
        builds one such mirror per replica row (on the row's 1-D submesh)
        — see :class:`ReplicatedDeviceSet`."""
        assert self.shardable(mesh.shape[axis]), (
            f"2^{self.t} z-groups do not split over {mesh.shape[axis]} shards"
        )
        return dataclasses.replace(
            self,
            vals=jax.device_put(self.vals, NamedSharding(mesh, P(axis, None))),
            images=jax.device_put(
                self.images, NamedSharding(mesh, P(axis, None, None))),
        )

    def place(self, device) -> "DeviceSet":
        """Single-device mirror committed to ``device``.

        The topology layer uses this to build one plain mirror per replica
        row, so balancer-dispatched single-device buckets execute on their
        assigned replica without a per-call transfer."""
        return dataclasses.replace(
            self,
            vals=jax.device_put(self.vals, device),
            images=jax.device_put(self.images, device),
        )


@dataclasses.dataclass(frozen=True)
class ReplicatedDeviceSet:
    """Per-replica-row mirrors of one set — the 2-D topology's unit of
    replication.

    ``rows[r]`` is replica row ``r``'s mirror: z-sharded over the row's
    1-D submesh when the topology has ``shards > 1``, committed to the
    row's anchor device otherwise.  Exposes the planner-visible metadata
    (``t`` / ``gmax`` / ``n``) of row 0 — identical on every row — so the
    shared ``(t, n)`` sort key and the shape-signature check treat it
    exactly like a :class:`DeviceSet`.
    """

    rows: Tuple[DeviceSet, ...]

    def row(self, r: int) -> DeviceSet:
        return self.rows[r]

    @property
    def t(self) -> int:
        return self.rows[0].t

    @property
    def gmax(self) -> int:
        return self.rows[0].gmax

    @property
    def n(self) -> int:
        return self.rows[0].n


def set_sort_key(s) -> Tuple[int, int]:
    """THE canonical set ordering key, ``(t, n)``: ascending partition depth
    (prefix alignment needs t ascending) with set size breaking ties, so the
    base set (index 0 after sorting) is the smallest.  Every layer — the
    planner (which appends the term as a final tie-break), the batched
    executor, and the sharded pipeline — must sort with this one helper;
    diverging keys let equal-``t`` sets pick a different base set than the
    plan's cache key and stats assume."""
    return (s.t, s.n)


def gmax_tier(gmax: int) -> int:
    """Static-shape tier for a set's max group size: next power of two
    (>= 8).  Device mirrors pad to this, and the planner keys shape
    signatures by it, so host-exact gmaxes never fragment the buckets."""
    return 1 << max(3, (int(gmax) - 1).bit_length())


def default_capacity(ts: Tuple[int, ...]) -> int:
    """Survivor-buffer (capacity) tier for a query shape.

    capacity ≈ E[survivors]: non-empty-intersection groups ≲ r_max + the
    false-positive rate * G; G/4 + floor is conservative for the paper's
    r << n regime, and preserves the work-saving — phase 2 runs on capacity
    group tuples, not all G.  Dense queries (frequent-term pairs, survivors
    ≈ G) overflow and are re-run once at full capacity by the executor.
    Deterministic in ``ts`` so it can key shape buckets."""
    return max(64, (1 << ts[-1]) // 4)


def default_capacity_per_shard(ts: Tuple[int, ...], n_shards: int,
                               capacity: Optional[int] = None) -> int:
    """Per-shard survivor-buffer tier for the sharded pipeline.

    The whole-query capacity budget — ``capacity`` when given (e.g. a
    learned ``ShapeSig.capacity_tier`` from ``exec/adaptive.py``), else
    :func:`default_capacity` — divided over the shards (survivors
    distribute ~uniformly because ``g`` randomizes z), floored, and never
    beyond the local group count ``G / n_shards`` (overflow past that is
    impossible).  Deterministic in ``(ts, n_shards, capacity)`` so
    ``(ShapeSig, shards)`` fully determines the executable's shapes.
    """
    local_g = (1 << ts[-1]) // n_shards
    whole = default_capacity(ts) if capacity is None else int(capacity)
    return min(local_g, max(16, whole // n_shards))


def _aligned_images(images: Sequence[jnp.ndarray], ts: Tuple[int, ...]) -> jnp.ndarray:
    """Stack per-set images aligned by prefix (z_i = z_k >> (t_k - t_i)):
    (G_i, m, W) each -> (k, G, m, W), or (B, G_i, m, W) -> (B, k, G, m, W).

    The largest set's images are used in place; the others are gathered.  A
    gather of 2^{t_k - t_i} repeated rows is a broadcast in disguise — XLA
    lowers it to one; we reshape+broadcast explicitly to keep HLO bytes
    honest (no gather scatter overhead in the roofline).
    """
    tk = ts[-1]
    out = []
    for img, t in zip(images, ts):
        if t == tk:
            out.append(img)
        else:
            rep = 1 << (tk - t)
            *lead, g, m, w = img.shape
            rep_img = jnp.broadcast_to(
                img[..., :, None, :, :], (*lead, g, rep, m, w)
            )
            out.append(rep_img.reshape(*lead, g * rep, m, w))
    return jnp.stack(out, axis=-4)


@functools.partial(
    jax.jit,
    static_argnames=("ts", "gmaxes", "capacity", "use_pallas",
                     "trace_counter"),
)
def _intersect_k_batch(
    vals: Tuple[Tuple[jnp.ndarray, ...], ...],
    images: Tuple[Tuple[jnp.ndarray, ...], ...],
    ts: Tuple[int, ...],
    gmaxes: Tuple[int, ...],
    capacity: int,
    use_pallas,
    trace_counter: str = "batch_traces",
):
    """One jit execution for a whole same-signature bucket of B queries.

    ``vals[i]``: B arrays of (2^{t_i}, gmax_i) int32; ``images[i]``: B arrays
    of (2^{t_i}, m, W).  The (B, …) stacking happens INSIDE the jit — the
    inputs are already device-resident DeviceSet rows, so stacking eagerly
    would cost 2k extra dispatches per call; fused here it is free.
    Returns (packed, r, n_surv, overflow) with a leading B axis each.
    ``trace_counter`` names the retrace telemetry bucket — the 2-D
    pipeline's single-device rows pass ``"mesh2d_traces"`` so its compiles
    are reported under the subsystem that owns them (being static, it also
    keeps the two paths' executables in separate cache entries).
    """
    EXEC_COUNTERS[trace_counter] += 1  # python side effect: trace-time only
    vals = tuple(jnp.stack(v) for v in vals)
    images = tuple(jnp.stack(im) for im in images)
    tk = ts[-1]
    G = 1 << tk
    B = vals[0].shape[0]
    imgs = _aligned_images(images, ts)                          # (B, k, G, m, W)
    passed = ops.bitmap_filter(imgs, use_pallas)                # (B, G)
    n_surv = passed.sum(axis=1)
    # survivor compaction without per-query nonzero: sort survivor positions
    # (non-survivors keyed G) so every row yields its first `capacity`
    # survivor indices, G-filled past the end — identical to
    # nonzero(size=capacity, fill_value=G) but trivially batched.
    pos = jnp.where(passed, jnp.arange(G, dtype=jnp.int32)[None, :], G)
    surv = setops.sort_rows(pos)
    if capacity <= G:
        surv = surv[:, :capacity]
    else:
        surv = jnp.pad(surv, ((0, 0), (0, capacity - G)), constant_values=G)
    valid_row = surv < G
    surv_c = jnp.minimum(surv, G - 1)
    rows = jnp.arange(B)[:, None]
    base = vals[0][rows, surv_c >> (tk - ts[0])]                # (B, cap, g0)
    keep = valid_row[:, :, None] & (base != -1)
    for v, t in zip(vals[1:], ts[1:]):
        other = v[rows, surv_c >> (tk - t)]                     # (B, cap, gi)
        keep = keep & ops.group_match(base, other, use_pallas)
    r = keep.sum(axis=(1, 2))
    overflow = n_surv > capacity
    # pack result values and mask into one buffer (-1 = dropped) so the
    # host round-trip is a single transfer per bucket
    packed = jnp.where(keep, base, -1)
    return packed, r, n_surv, overflow


def _signature(sets: Sequence[DeviceSet]) -> Tuple[Tuple[int, ...], Tuple[int, ...]]:
    return tuple(s.t for s in sets), tuple(s.gmax for s in sets)


class PassRecord(NamedTuple):
    """One device pass of a bucket, on the ``time.perf_counter`` clock:
    pass number (0 the first, 1 the overflow re-run), the query rows it
    ran, its survivor capacity, when it was issued, and when its blocking
    fetch began and ended.  The exec layer turns these into spans."""

    pass_no: int
    rows: int
    capacity: int
    t_issue: float
    t_fetch: float
    t_fetched: float


def _fetch_pass(passes: List[PassRecord], handles, rows: int, capacity: int,
                t_issue: float):
    """Block for one pass's device buffers (``jax.device_get``) and append
    its :class:`PassRecord` to ``passes``; every collect loop fetches
    through here."""
    t_fetch = time.perf_counter()
    fetched = jax.device_get(handles)
    passes.append(PassRecord(len(passes), rows, capacity, t_issue, t_fetch,
                             time.perf_counter()))
    return fetched


@dataclasses.dataclass
class PendingBatch:
    """In-flight handle for one dispatched bucket pass.

    JAX dispatch is asynchronous: the jit call returns device arrays that
    are *futures* — compute proceeds while the host does other work, and
    only ``jax.device_get`` blocks.  ``dispatch_*_batch`` issues the first
    pass and wraps its handles here; :meth:`collect` performs the deferred
    transfer, the host-side result processing, and the (rare) overflow
    re-run passes, returning exactly what the synchronous
    ``intersect_*_batch`` returns.  Overflow re-runs issue new jit calls
    from inside collect — they resolve against the already-captured
    DeviceSet rows, so collect never needs the dispatcher's locks.

    ``handles`` is the first pass's raw output pytree; :meth:`is_ready`
    polls it without blocking (a non-blocking peek for schedulers that
    want to collect completed buckets first).  :meth:`collect` is
    memoized — calling it twice returns the same result list.

    ``passes`` fills as :meth:`collect` fetches: one :class:`PassRecord`
    per device pass, so a caller can tell the first pass's transfer from
    an overflow re-run's issue and transfer.
    """

    n_queries: int
    handles: object = None
    _collect: Optional[Callable[[], List[Tuple[np.ndarray, Dict]]]] = None
    _results: Optional[List[Tuple[np.ndarray, Dict]]] = None
    passes: List[PassRecord] = dataclasses.field(default_factory=list)

    def is_ready(self) -> bool:
        """True when every first-pass device buffer has materialized (a
        collect would not block on the transfer; overflow re-runs can
        still add work).  Conservatively True for handle types without
        ``is_ready`` (e.g. already-fetched results)."""
        if self._results is not None:
            return True
        for leaf in jax.tree_util.tree_leaves(self.handles):
            ready = getattr(leaf, "is_ready", None)
            if ready is not None and not ready():
                return False
        return True

    def collect(self) -> List[Tuple[np.ndarray, Dict]]:
        """Block for the results: device transfer + overflow re-runs +
        host post-processing.  Returns [(sorted values, stats), ...] in
        query order (memoized)."""
        if self._results is None:
            self._results = self._collect()
            self._collect = None  # drop closed-over device handles
            self.handles = None
        return self._results


def dispatch_device_batch(
    queries: Sequence[Sequence[DeviceSet]],
    capacity: Optional[int] = None,
    use_pallas="auto",
) -> PendingBatch:
    """Issue the first pass of a same-signature bucket without blocking.

    The asynchronous half of :func:`intersect_device_batch`: validates the
    bucket, issues ONE jit execution for the first pass (JAX returns
    immediately — the arrays are futures), and returns a
    :class:`PendingBatch` whose :meth:`~PendingBatch.collect` finishes the
    job (transfer, overflow re-runs, result assembly).  Counter semantics
    are unchanged: ``batch_calls`` per pass (the first bumps at dispatch
    time, re-run passes bump inside collect), ``rerun_calls`` per overflow
    pass.
    """
    if not len(queries):
        return PendingBatch(n_queries=0, _collect=lambda: [])
    ordered = [sorted(q, key=set_sort_key) for q in queries]
    ts, gmaxes = _signature(ordered[0])
    for q in ordered[1:]:
        assert _signature(q) == (ts, gmaxes), "bucket mixes shape signatures"
    G = 1 << ts[-1]

    def issue(active: List[int], cap: int):
        b_tier = 1 << (len(active) - 1).bit_length()  # pad B to a pow2 tier
        rows = active + [active[0]] * (b_tier - len(active))
        vals = tuple(
            tuple(ordered[i][j].vals for i in rows) for j in range(len(ts))
        )
        images = tuple(
            tuple(ordered[i][j].images for i in rows) for j in range(len(ts))
        )
        EXEC_COUNTERS["batch_calls"] += 1
        return _intersect_k_batch(vals, images, ts, gmaxes, cap, use_pallas)

    first_active = list(range(len(ordered)))
    first_cap = capacity or default_capacity(ts)
    passes: List[PassRecord] = []
    first_issue = time.perf_counter()
    first_handles = issue(first_active, first_cap)

    def collect() -> List[Tuple[np.ndarray, Dict]]:
        results: List[Optional[Tuple[np.ndarray, Dict]]] = [None] * len(ordered)
        active, cap, handles = first_active, first_cap, first_handles
        t_issue = first_issue
        while True:
            packed_h, r_h, n_surv_h, over_h = _fetch_pass(
                passes, handles, len(active), cap, t_issue)
            rerun = []
            for row, qi in enumerate(active):
                if over_h[row]:
                    rerun.append(qi)
                    continue
                row_vals = packed_h[row].ravel()
                out = row_vals[row_vals != -1]
                results[qi] = (
                    np.sort(out.astype(np.uint32)),
                    {
                        "group_tuples": G,
                        "tuples_survived": int(n_surv_h[row]),
                        "capacity": cap,
                        "r": int(r_h[row]),
                        "batch_size": len(active),
                    },
                )
            if not rerun:
                return results  # type: ignore[return-value]
            active = rerun
            cap = G  # rare path: ONE re-run of the overflow subset at G
            EXEC_COUNTERS["rerun_calls"] += 1
            t_issue = time.perf_counter()
            handles = issue(active, cap)

    return PendingBatch(n_queries=len(ordered), handles=first_handles,
                        _collect=collect, passes=passes)


def intersect_device_batch(
    queries: Sequence[Sequence[DeviceSet]],
    capacity: Optional[int] = None,
    use_pallas="auto",
) -> List[Tuple[np.ndarray, Dict]]:
    """Intersect B same-signature queries in one jit execution each pass.

    Every query is a list of DeviceSets; all queries must share the shape
    signature ``(ts, gmaxes)`` after the (t, n)-sort — the exec layer's
    bucketing guarantees this.  Overflowing queries (survivors > capacity)
    are re-run as ONE enlarged subset pass at capacity G, where overflow is
    impossible — a single extra jit execution per bucket, never a cascade
    of doublings.

    The batch dim is quantized: B pads up to a power of two by repeating
    the first query's rows (references to the same device arrays — the only
    cost is the fused in-jit stack).  Without this every distinct
    (signature, B) pair — including every overflow-subset size — would be
    its own executable; with it the cache holds at most log2(B_max)
    executables per signature.  Padding rows are dropped before results
    materialize.

    The synchronous composition of :func:`dispatch_device_batch` +
    :meth:`PendingBatch.collect` — callers that can overlap buckets use
    the two halves directly.

    Returns a list of (sorted result values, stats dict) in query order.
    """
    return dispatch_device_batch(
        queries, capacity=capacity, use_pallas=use_pallas
    ).collect()


def intersect_device(
    sets: Sequence[DeviceSet],
    capacity: Optional[int] = None,
    use_pallas="auto",
):
    """Intersect k device sets; returns (values, count) on host + stats.

    A batch of one through :func:`intersect_device_batch` — single queries
    and bucketed batches share the same jit cache (keyed additionally by B).
    """
    (result, stats), = intersect_device_batch(
        [list(sets)], capacity=capacity, use_pallas=use_pallas
    )
    return result, stats


def pow2_tiers(up_to: int) -> Tuple[int, ...]:
    """All power-of-two batch tiers ``(1, 2, 4, …, up_to)``.

    Warming these covers every partial-flush size in ``[1, up_to]`` (the
    executor pads B up to the next power of two), so a front-end with
    ``flush_tier = up_to`` compiles nothing at serve time.
    """
    assert up_to >= 1 and (up_to & (up_to - 1)) == 0, "up_to must be pow2"
    tiers, b = [], 1
    while b <= up_to:
        tiers.append(b)
        b <<= 1
    return tuple(tiers)


def bucket_hlo_text(
    queries: Sequence[Sequence[DeviceSet]],
    capacity: Optional[int] = None,
    use_pallas="auto",
) -> str:
    """Optimized (post-XLA) HLO text for one bucket's jit executable.

    Lowers and compiles ``_intersect_k_batch`` for the bucket exactly as
    :func:`dispatch_device_batch` would execute it (same signature, same
    pow2 B-tier padding, same capacity default) and returns the compiled
    module text — the input ``launch/hlo_analysis.py::analyze_hlo`` wants,
    so benchmarks can report analytical FLOP/byte summaries for the
    executable they actually measured.  Shares the process jit cache with
    live execution; tracing bumps ``EXEC_COUNTERS["batch_traces"]`` like
    any other trace (lower before measuring, or reset counters after).
    """
    assert len(queries), "need at least one query row to lower"
    ordered = [sorted(q, key=set_sort_key) for q in queries]
    ts, gmaxes = _signature(ordered[0])
    for q in ordered[1:]:
        assert _signature(q) == (ts, gmaxes), "bucket mixes shape signatures"
    cap = capacity or default_capacity(ts)
    b_tier = 1 << (len(ordered) - 1).bit_length()
    rows = list(range(len(ordered))) + [0] * (b_tier - len(ordered))
    vals = tuple(
        tuple(ordered[i][j].vals for i in rows) for j in range(len(ts))
    )
    images = tuple(
        tuple(ordered[i][j].images for i in rows) for j in range(len(ts))
    )
    lowered = _intersect_k_batch.lower(vals, images, ts, gmaxes, cap,
                                       use_pallas)
    return lowered.compile().as_text()


def warm_executables(
    representatives: Sequence[Sequence[DeviceSet]],
    b_tiers: Sequence[int] = (1,),
    capacity: Optional[int] = None,
    use_pallas="auto",
    mesh: Optional[Mesh] = None,
    axis: str = SHARD_AXIS,
    topology=None,
) -> int:
    """Pre-trace the bucketed pipeline so first live requests don't compile.

    ``representatives`` holds ONE query row (list of DeviceSets) per shape
    signature worth warming — typically the top-K signatures of a sample
    workload, extracted at index-build time.  For each row and each batch
    tier ``b`` in ``b_tiers`` the row is replicated ``b`` times and pushed
    through :func:`intersect_device_batch`, populating the jit cache for the
    ``(ShapeSig, B-tier)`` executable that a live bucket of up to ``b``
    queries will hit (the executor pads B up to a power of two, so warming
    tier ``b`` covers every partial flush of size in ``(b/2, b]``).

    With ``mesh`` set, the rows are pushed through
    :func:`intersect_sharded_batch` instead, warming the sharded
    ``(ShapeSig, B-tier, shards)`` executables — pass the z-sharded mirrors
    as representatives so the warmed executable sees serving-time shardings.
    With ``topology`` set (2-D), the rows warm
    :func:`intersect_mesh2d_batch` the same way — pass
    :class:`ReplicatedDeviceSet` mirrors; one warming execution covers
    every replica row's executable, since the 2-D pipeline dispatches all
    rows per pass.

    Results are discarded — this warms the *compile* cache, not the result
    cache.  Increments ``EXEC_COUNTERS["warm_executions"]`` once per
    (row, tier) execution; the underlying ``batch_calls`` / ``batch_traces``
    (or ``sharded_*``) bumps happen at build time, before serving counters
    are read.

    Returns the number of pipeline executions issued.
    """
    issued = 0
    for row in representatives:
        for b in b_tiers:
            assert b >= 1 and (b & (b - 1)) == 0, "b_tiers must be powers of two"
            if topology is not None:
                intersect_mesh2d_batch(
                    [list(row)] * b, topology, capacity_per_shard=capacity,
                    use_pallas=use_pallas,
                )
            elif mesh is not None:
                intersect_sharded_batch(
                    [list(row)] * b, mesh, axis=axis,
                    capacity_per_shard=capacity, use_pallas=use_pallas,
                )
            else:
                intersect_device_batch(
                    [list(row)] * b, capacity=capacity, use_pallas=use_pallas
                )
            EXEC_COUNTERS["warm_executions"] += 1
            issued += 1
    return issued


def warm_from_plans(plans, get_set, top_k: int = 8,
                    b_tiers: Sequence[int] = (1,), use_pallas="auto",
                    mesh: Optional[Mesh] = None, axis: str = SHARD_AXIS,
                    get_sharded_set=None, topology=None,
                    get_replica_set=None):
    """Shared warming policy over already-planned queries.

    Counts device-routed shape signatures in ``plans`` (objects with
    ``.algorithm`` / ``.sig`` / ``.terms`` — i.e. ``exec.plan.QueryPlan``),
    picks the ``top_k`` most frequent, and pre-traces one representative
    row per signature at every batch tier in ``b_tiers`` via
    :func:`warm_executables`.  ``get_set`` maps a planned term to its
    DeviceSet; signatures routed sharded (``sig.shards > 1``) resolve
    through ``get_sharded_set`` (falling back to ``get_set``) and warm the
    ``(ShapeSig, B-tier, shards)`` executable on ``mesh`` instead.

    With a ``topology`` (2-D ``exec.topology.Topology``), mesh-routed
    signatures (``shards > 1`` or ``replicas > 1``) warm the 2-D pipeline
    on ``topology.mesh``, and single-device signatures warm on EVERY
    replica row via ``get_replica_set(r, term)`` — jit executables are
    placement-keyed, so warming only replica 0 would leave the balancer's
    other targets compiling at first live dispatch.  Returns the warmed
    signatures, most frequent first.
    """
    from collections import Counter

    freq = Counter(p.sig for p in plans if p.algorithm == "device")
    rep_terms = {}
    for p in plans:
        if p.algorithm == "device" and p.sig not in rep_terms:
            rep_terms[p.sig] = p.terms
    warmed = [sig for sig, _ in freq.most_common(top_k)]
    for sig in warmed:
        # warm at the SIGNATURE's capacity tier, not the executor default —
        # with an adaptive capacity model the plan's tier is the learned
        # one, and warming any other tier would trace an executable no
        # live bucket ever runs (the sharded paths derive their per-shard
        # buffer from the same tier, mirroring execute_bucket)
        shards = getattr(sig, "shards", 1)
        replicas = getattr(sig, "replicas", 1)
        capacity = getattr(sig, "capacity_tier", None)
        terms = rep_terms[sig]
        mesh_routed = shards > 1 or (topology is not None and replicas > 1)
        eshape = getattr(sig, "eshape", None)
        if eshape is not None:
            # expression signature: warm the expression evaluator(s).  The
            # row is the plan's leaf terms in TRAVERSAL order (never
            # sorted); mesh-routed shapes warm the sharded / 2-D twins.
            for b in b_tiers:
                if shards > 1 or (topology is not None and replicas > 1):
                    cap = (None if capacity is None else
                           default_expr_capacity_per_shard(
                               sig.ts, sig.gmaxes, shards, capacity=capacity))
                    resolve = get_sharded_set or get_set
                    row = [resolve(t) for t in terms]
                    if topology is not None:
                        intersect_expr_mesh2d_batch(
                            [list(row)] * b, eshape, topology,
                            capacity_per_shard=cap)
                    else:
                        intersect_expr_sharded_batch(
                            [list(row)] * b, eshape, mesh, axis=axis,
                            capacity_per_shard=cap)
                elif (topology is not None and topology.replicas > 1
                      and get_replica_set is not None):
                    for r in range(topology.replicas):
                        row = [get_replica_set(r, t) for t in terms]
                        intersect_expr_batch([list(row)] * b, eshape,
                                             capacity=capacity)
                else:
                    row = [get_set(t) for t in terms]
                    intersect_expr_batch([list(row)] * b, eshape,
                                         capacity=capacity)
                EXEC_COUNTERS["warm_executions"] += 1
            continue
        cands = getattr(sig, "cands", 0)
        if cands > 0:
            # count (suggest) signature: terms[0] is the probe, terms[1:]
            # the candidate representatives, and ``capacity_tier`` holds
            # the top-K selection tier (the count path has no survivor
            # buffer).  Route exactly as live dispatch will.
            k = capacity or 8
            for b in b_tiers:
                if mesh_routed and topology is not None:
                    resolve = get_sharded_set or get_set
                    row = (resolve(terms[0]), [resolve(t) for t in terms[1:]])
                    intersect_count_mesh2d_batch(
                        [row] * b, k, topology, use_pallas=use_pallas)
                elif shards > 1:
                    resolve = get_sharded_set or get_set
                    row = (resolve(terms[0]), [resolve(t) for t in terms[1:]])
                    intersect_count_sharded_batch(
                        [row] * b, k, mesh, axis=axis, use_pallas=use_pallas)
                elif (topology is not None and topology.replicas > 1
                      and get_replica_set is not None):
                    for r in range(topology.replicas):
                        row = (get_replica_set(r, terms[0]),
                               [get_replica_set(r, t) for t in terms[1:]])
                        intersect_count_batch(
                            [row] * b, k, use_pallas=use_pallas)
                else:
                    row = (get_set(terms[0]), [get_set(t) for t in terms[1:]])
                    intersect_count_batch([row] * b, k, use_pallas=use_pallas)
                EXEC_COUNTERS["warm_executions"] += 1
            continue
        if mesh_routed:
            if capacity is not None:
                capacity = default_capacity_per_shard(
                    sig.ts, shards, capacity=capacity)
            resolve = get_sharded_set or get_set
            warm_executables(
                [[resolve(t) for t in terms]], b_tiers=b_tiers,
                capacity=capacity, use_pallas=use_pallas,
                topology=topology, mesh=mesh if topology is None else None,
                axis=axis,
            )
        elif (topology is not None and topology.replicas > 1
              and get_replica_set is not None):
            for r in range(topology.replicas):
                warm_executables(
                    [[get_replica_set(r, t) for t in terms]],
                    b_tiers=b_tiers, capacity=capacity,
                    use_pallas=use_pallas,
                )
        else:
            warm_executables(
                [[get_set(t) for t in terms]], b_tiers=b_tiers,
                capacity=capacity, use_pallas=use_pallas,
            )
    return warmed


def clear_exec_jit_cache() -> None:
    """Drop every compiled executable of the bucketed pipeline.

    Test hook: makes "warming traces, serving doesn't" assertions
    deterministic regardless of what earlier tests compiled (the jit cache
    is process-global).  Clears the sharded pipeline's cache too — the 2-D
    pipeline's row executables live in the same two jits (keyed apart by
    their ``trace_counter``), so they are covered.
    """
    for fn in (_intersect_k_batch, _intersect_k_sharded_batch,
               _eval_expr_batch, _eval_expr_sharded_batch,
               _intersect_count_batch, _intersect_count_sharded_batch):
        fn.clear_cache()


# --------------------------------------------------------------------------
# shard_map distribution over the z-prefix space
# --------------------------------------------------------------------------

def make_shard_mesh(n_shards: Optional[int] = None,
                    axis: str = SHARD_AXIS) -> Mesh:
    """1-D mesh over the first ``n_shards`` local devices (all by default),
    named ``axis`` — the mesh shape :func:`intersect_sharded_batch` and the
    engines' ``mesh=`` parameters expect.  On CPU, export
    ``XLA_FLAGS=--xla_force_host_platform_device_count=N`` *before* the
    first jax call to get N host devices to shard over."""
    devices = jax.devices()
    n = len(devices) if n_shards is None else int(n_shards)
    assert 1 <= n <= len(devices), f"need {n} devices, have {len(devices)}"
    return Mesh(np.asarray(devices[:n]), (axis,))


def make_mesh2d(replicas: int, shards: Optional[int] = None,
                data_axis: str = DATA_AXIS,
                shard_axis: str = SHARD_AXIS) -> Mesh:
    """2-D ``(data, shard)`` device mesh: ``replicas`` data-parallel rows of
    ``shards`` z-sharding columns each (``shards`` defaults to using every
    local device).  Row ``r`` holds one full replica of the posting
    mirrors (:meth:`DeviceSet.shard` on this mesh replicates over ``data``
    and partitions z over ``shard``); :func:`intersect_mesh2d_batch` splits
    a bucket's batch axis over the rows.  ``replicas`` must be a power of
    two so the executor's pow2 batch tiers always divide evenly over the
    data axis.  The 1-D special cases degenerate cleanly: ``replicas = 1``
    is pure z-sharding, ``shards = 1`` is pure data parallelism."""
    devices = jax.devices()
    replicas = int(replicas)
    shards = (len(devices) // replicas) if shards is None else int(shards)
    n = replicas * shards
    assert replicas >= 1 and shards >= 1 and n <= len(devices), (
        f"need {replicas}x{shards} = {n} devices, have {len(devices)}"
    )
    assert replicas & (replicas - 1) == 0, (
        "replicas must be a power of two (batch tiers are pow2)"
    )
    grid = np.asarray(devices[:n]).reshape(replicas, shards)
    return Mesh(grid, (data_axis, shard_axis))


def _local_shard_block(lvals, limages, ts, capacity_per_shard, use_pallas):
    """One shard's local two-phase block, shared by the 1-D and 2-D
    shard_map pipelines: phase-1 filter over the local z range, sort-
    compaction into the per-shard survivor buffer, phase-2 all-pairs match.

    ``lvals[i]``: (B_local, 2^t_i / n_shards, gmax_i); ``limages[i]``:
    (B_local, 2^t_i / n_shards, m, W).  Returns (packed, r, n_surv,
    overflow) with a leading B_local axis each — the caller adds whatever
    shard/replica axes its out_specs need.
    """
    tk = ts[-1]
    G_local = limages[-1].shape[1]
    B = lvals[0].shape[0]
    imgs = _aligned_images(limages, ts)                 # (B, k, Gl, m, W)
    passed = ops.bitmap_filter(imgs, use_pallas)        # (B, Gl)
    n_surv = passed.sum(axis=1)
    pos = jnp.where(passed, jnp.arange(G_local, dtype=jnp.int32)[None, :],
                    G_local)
    # the caller clamps capacity_per_shard to the local group count, so a
    # plain slice always suffices (no pad branch, unlike the unsharded
    # pipeline where capacity may exceed G)
    assert capacity_per_shard <= G_local, "caller must clamp to local G"
    surv = setops.sort_rows(pos)[:, :capacity_per_shard]
    valid_row = surv < G_local
    surv_c = jnp.minimum(surv, G_local - 1)
    rows = jnp.arange(B)[:, None]
    base = lvals[0][rows, surv_c >> (tk - ts[0])]       # (B, cap, g0)
    keep = valid_row[:, :, None] & (base != -1)
    for v, t in zip(lvals[1:], ts[1:]):
        other = v[rows, surv_c >> (tk - t)]
        keep = keep & ops.group_match(base, other, use_pallas)
    r = keep.sum(axis=(1, 2))
    overflow = n_surv > capacity_per_shard
    packed = jnp.where(keep, base, -1)
    return packed, r, n_surv, overflow


@functools.partial(
    jax.jit,
    static_argnames=("mesh", "axis", "ts", "gmaxes", "capacity_per_shard",
                     "use_pallas", "trace_counter"),
)
def _intersect_k_sharded_batch(
    vals: Tuple[Tuple[jnp.ndarray, ...], ...],
    images: Tuple[Tuple[jnp.ndarray, ...], ...],
    mesh: Mesh,
    axis: str,
    ts: Tuple[int, ...],
    gmaxes: Tuple[int, ...],
    capacity_per_shard: int,
    use_pallas,
    trace_counter: str = "sharded_traces",
):
    """One jit execution of a same-signature bucket, z-sharded over ``mesh``.

    The sharded twin of :func:`_intersect_k_batch`: inputs are B
    device-resident DeviceSet rows per set (z-sharded mirrors), stacked
    inside the jit to ``(B, 2^t_i, …)`` arrays whose z axis is partitioned
    over ``mesh[axis]``.  Both phases run per shard with NO communication —
    Theorem 3.7's alignment means a shard's local z_k range maps into the
    same shard's z_i range (valid whenever n_shards divides 2^{t_0}) — and
    each shard compacts its own survivors into a local
    ``capacity_per_shard`` buffer by the same sort-compaction as the
    unsharded path.

    Returns (packed, r, n_surv, overflow):

    - ``packed``  (B, n_shards * capacity_per_shard, gmax_0) — per-shard
      result buffers concatenated along the capacity axis (-1 = dropped);
      one transfer materializes the whole bucket.
    - ``r`` / ``n_surv`` / ``overflow`` — (n_shards, B) per-(shard, query):
      exact-match count, phase-1 survivor count, and the overflow flag
      ``n_surv > capacity_per_shard`` that drives the host-side re-run.
    """
    EXEC_COUNTERS[trace_counter] += 1  # python side effect: trace-time only
    vals = tuple(jnp.stack(v) for v in vals)        # (B, 2^t_i, gmax_i)
    images = tuple(jnp.stack(im) for im in images)  # (B, 2^t_i, m, W)
    k = len(ts)

    def local_fn(*flat):
        packed, r, n_surv, overflow = _local_shard_block(
            flat[:k], flat[k:], ts, capacity_per_shard, use_pallas)
        # leading length-1 shard axis on the per-shard scalars so out_specs
        # can concatenate them into (n_shards, B) without communication
        return packed, r[None], n_surv[None], overflow[None]

    in_specs = tuple([P(None, axis)] * (2 * k))
    out_specs = (P(None, axis), P(axis), P(axis), P(axis))
    fn = jax.shard_map(local_fn, mesh=mesh, in_specs=in_specs,
                       out_specs=out_specs, check_vma=False)
    return fn(*vals, *images)


def dispatch_sharded_batch(
    queries: Sequence[Sequence[DeviceSet]],
    mesh: Mesh,
    axis: str = SHARD_AXIS,
    capacity_per_shard: Optional[int] = None,
    use_pallas="auto",
) -> PendingBatch:
    """Issue the first z-sharded pass of a bucket without blocking.

    The asynchronous half of :func:`intersect_sharded_batch` — see
    :func:`dispatch_device_batch` for the dispatch/collect contract.
    Counters: ``sharded_calls`` per pass, ``sharded_rerun_calls`` per
    overflow pass (bumped inside collect).
    """
    if not len(queries):
        return PendingBatch(n_queries=0, _collect=lambda: [])
    n_shards = mesh.shape[axis]
    ordered = [sorted(q, key=set_sort_key) for q in queries]
    ts, gmaxes = _signature(ordered[0])
    for q in ordered[1:]:
        assert _signature(q) == (ts, gmaxes), "bucket mixes shape signatures"
    assert (1 << ts[0]) % n_shards == 0, (
        f"smallest set (t={ts[0]}) must split over {n_shards} shards"
    )
    G = 1 << ts[-1]
    G_local = G // n_shards

    def issue(active: List[int], cap: int):
        b_tier = 1 << (len(active) - 1).bit_length()  # pad B to a pow2 tier
        rows = active + [active[0]] * (b_tier - len(active))
        vals = tuple(
            tuple(ordered[i][j].vals for i in rows) for j in range(len(ts))
        )
        images = tuple(
            tuple(ordered[i][j].images for i in rows) for j in range(len(ts))
        )
        EXEC_COUNTERS["sharded_calls"] += 1
        return _intersect_k_sharded_batch(
            vals, images, mesh, axis, ts, gmaxes, cap, use_pallas
        )

    first_active = list(range(len(ordered)))
    first_cap = min(
        capacity_per_shard or default_capacity_per_shard(ts, n_shards),
        G_local,
    )
    passes: List[PassRecord] = []
    first_issue = time.perf_counter()
    first_handles = issue(first_active, first_cap)

    def collect() -> List[Tuple[np.ndarray, Dict]]:
        results: List[Optional[Tuple[np.ndarray, Dict]]] = [None] * len(ordered)
        active, cap, handles = first_active, first_cap, first_handles
        t_issue = first_issue
        while True:
            packed_h, r_h, n_surv_h, over_h = _fetch_pass(
                passes, handles, len(active), cap, t_issue)
            rerun = []
            for row, qi in enumerate(active):
                if over_h[:, row].any():
                    rerun.append(qi)
                    continue
                row_vals = packed_h[row].ravel()
                out = row_vals[row_vals != -1]
                results[qi] = (
                    np.sort(out.astype(np.uint32)),
                    {
                        "group_tuples": G,
                        "tuples_survived": int(n_surv_h[:, row].sum()),
                        "max_shard_survivors": int(n_surv_h[:, row].max()),
                        "capacity_per_shard": cap,
                        "n_shards": n_shards,
                        "r": int(r_h[:, row].sum()),
                        "batch_size": len(active),
                    },
                )
            if not rerun:
                return results  # type: ignore[return-value]
            active = rerun
            cap = G_local  # rare path: one re-run at local G, no overflow
            EXEC_COUNTERS["sharded_rerun_calls"] += 1
            t_issue = time.perf_counter()
            handles = issue(active, cap)

    return PendingBatch(n_queries=len(ordered), handles=first_handles,
                        _collect=collect, passes=passes)


def intersect_sharded_batch(
    queries: Sequence[Sequence[DeviceSet]],
    mesh: Mesh,
    axis: str = SHARD_AXIS,
    capacity_per_shard: Optional[int] = None,
    use_pallas="auto",
) -> List[Tuple[np.ndarray, Dict]]:
    """Intersect B same-signature queries z-sharded over a device mesh.

    The sharded bucket executor: same contract as
    :func:`intersect_device_batch` (signature-uniform queries, pow2 B-tier
    padding, packed single-transfer results, list of (sorted values, stats)
    in query order) with the z-prefix space partitioned over
    ``mesh[axis]``.  Communication-free by Theorem 3.7's alignment; only
    the compact per-shard result buffers leave their shard.

    Overflow is tracked per (query, shard): a query whose survivors exceed
    ``capacity_per_shard`` on ANY shard is re-run as ONE enlarged subset
    pass at the local group count ``G / n_shards``, where per-shard
    overflow is impossible — so results are always exact, never silently
    truncated.  Counters: ``sharded_calls`` per pass,
    ``sharded_rerun_calls`` per overflow pass, ``sharded_traces`` per
    compile — the sharded twins of the ``batch_*`` counters.

    Pass z-sharded mirrors (:meth:`DeviceSet.shard`) to keep posting data
    resident on its shard across calls; plain mirrors also work but are
    re-partitioned on entry.  The synchronous composition of
    :func:`dispatch_sharded_batch` + :meth:`PendingBatch.collect`.
    """
    return dispatch_sharded_batch(
        queries, mesh, axis=axis, capacity_per_shard=capacity_per_shard,
        use_pallas=use_pallas,
    ).collect()


def intersect_sharded(
    sets: Sequence[DeviceSet],
    mesh: Mesh,
    axis: str = SHARD_AXIS,
    capacity_per_shard: Optional[int] = None,
    use_pallas="auto",
):
    """Intersect k device sets z-sharded over ``mesh``; returns (values,
    stats) on host.

    A batch of one through :func:`intersect_sharded_batch` — the historical
    single-query sharded entry point, now overflow-exact (per-shard
    survivor counts past ``capacity_per_shard`` trigger the enlarged re-run
    instead of silently truncating results) and ordered by the shared
    ``(t, n)`` sort key the planner and batched executor use.
    """
    (result, stats), = intersect_sharded_batch(
        [list(sets)], mesh, axis=axis, capacity_per_shard=capacity_per_shard,
        use_pallas=use_pallas,
    )
    return result, stats


# --------------------------------------------------------------------------
# 2-D distribution: data-parallel replicas x z-sharding
# --------------------------------------------------------------------------

def dispatch_mesh2d_batch(
    queries: Sequence[Sequence[ReplicatedDeviceSet]],
    topology,
    capacity_per_shard: Optional[int] = None,
    use_pallas="auto",
) -> PendingBatch:
    """Issue the first 2-D pass of a bucket without blocking.

    The asynchronous half of :func:`intersect_mesh2d_batch` — see
    :func:`dispatch_device_batch` for the dispatch/collect contract.  A
    pass already issues all replica rows back-to-back before any
    transfer; this additionally defers the single collection point, so
    *different buckets* can have their rows in flight simultaneously.
    Counters: ``mesh2d_calls`` per pass, ``mesh2d_row_dispatches`` per row
    execution, ``mesh2d_rerun_calls`` per overflow pass (inside collect).
    """
    if not len(queries):
        return PendingBatch(n_queries=0, _collect=lambda: [])
    n_replicas = topology.replicas
    n_shards = topology.shards
    assert n_replicas & (n_replicas - 1) == 0, (
        "data axis must be a power of two (batch tiers are pow2)"
    )
    ordered = [sorted(q, key=set_sort_key) for q in queries]
    ts, gmaxes = _signature(ordered[0])
    for q in ordered[1:]:
        assert _signature(q) == (ts, gmaxes), "bucket mixes shape signatures"
    assert (1 << ts[0]) % n_shards == 0, (
        f"smallest set (t={ts[0]}) must split over {n_shards} shards"
    )
    G = 1 << ts[-1]
    G_local = G // n_shards

    def issue(active: List[int], cap: int):
        # pow2 B-tier, floored at the replica count so `data` splits evenly
        # into equal pow2 row slices (one executable shape per pass)
        b_tier = max(n_replicas, 1 << (len(active) - 1).bit_length())
        rows = active + [active[0]] * (b_tier - len(active))
        slice_len = b_tier // n_replicas
        EXEC_COUNTERS["mesh2d_calls"] += 1
        handles = {}
        for rr in range(n_replicas):
            if rr * slice_len >= len(active):
                continue  # slice is pure padding: nothing real to compute
            chunk = rows[rr * slice_len:(rr + 1) * slice_len]
            vals = tuple(
                tuple(ordered[i][j].row(rr).vals for i in chunk)
                for j in range(len(ts))
            )
            images = tuple(
                tuple(ordered[i][j].row(rr).images for i in chunk)
                for j in range(len(ts))
            )
            EXEC_COUNTERS["mesh2d_row_dispatches"] += 1
            if n_shards > 1:
                out = _intersect_k_sharded_batch(
                    vals, images, topology.row_mesh(rr),
                    topology.shard_axis, ts, gmaxes, cap, use_pallas,
                    trace_counter="mesh2d_traces",
                )
            else:
                packed, r, n_surv, overflow = _intersect_k_batch(
                    vals, images, ts, gmaxes, cap, use_pallas,
                    trace_counter="mesh2d_traces",
                )
                # single-shard layout: add the length-1 shard axis the
                # sharded kernel's (n_shards, B) outputs carry
                out = (packed, r[None], n_surv[None], overflow[None])
            handles[rr] = out
        return handles, slice_len

    first_active = list(range(len(ordered)))
    first_cap = min(
        capacity_per_shard or default_capacity_per_shard(ts, n_shards),
        G_local,
    )
    passes: List[PassRecord] = []
    first_issue = time.perf_counter()
    first_handles, first_slice_len = issue(first_active, first_cap)

    def collect() -> List[Tuple[np.ndarray, Dict]]:
        results: List[Optional[Tuple[np.ndarray, Dict]]] = [None] * len(ordered)
        active, cap = first_active, first_cap
        t_issue = first_issue
        handles, slice_len = first_handles, first_slice_len
        while True:
            # one collection point: every row was in flight before any
            # transfer started
            fetched = _fetch_pass(passes, handles, len(active), cap,
                                  t_issue)
            rerun = []
            for rr, (packed_h, r_h, n_surv_h, over_h) in fetched.items():
                chunk_start = rr * slice_len
                for local_row in range(slice_len):
                    pos = chunk_start + local_row
                    if pos >= len(active):
                        continue  # padding rows repeat query active[0]
                    qi = active[pos]
                    if over_h[:, local_row].any():
                        rerun.append(qi)
                        continue
                    row_vals = packed_h[local_row].ravel()
                    out_vals = row_vals[row_vals != -1]
                    results[qi] = (
                        np.sort(out_vals.astype(np.uint32)),
                        {
                            "group_tuples": G,
                            "tuples_survived": int(n_surv_h[:, local_row].sum()),
                            "max_shard_survivors": int(
                                n_surv_h[:, local_row].max()),
                            "capacity_per_shard": cap,
                            "n_shards": n_shards,
                            "n_replicas": n_replicas,
                            "replica": rr,
                            "r": int(r_h[:, local_row].sum()),
                            "batch_size": len(active),
                        },
                    )
            if not rerun:
                return results  # type: ignore[return-value]
            active = rerun
            cap = G_local  # rare path: one re-run at local G, no overflow
            EXEC_COUNTERS["mesh2d_rerun_calls"] += 1
            t_issue = time.perf_counter()
            handles, slice_len = issue(active, cap)

    return PendingBatch(n_queries=len(ordered), handles=first_handles,
                        _collect=collect, passes=passes)


def intersect_mesh2d_batch(
    queries: Sequence[Sequence[ReplicatedDeviceSet]],
    topology,
    capacity_per_shard: Optional[int] = None,
    use_pallas="auto",
) -> List[Tuple[np.ndarray, Dict]]:
    """Intersect B same-signature queries over a 2-D ``(data, shard)`` mesh.

    Same contract as :func:`intersect_sharded_batch` (signature-uniform
    queries, packed single-transfer results, list of (sorted values, stats)
    in query order) with the batch axis additionally split over the
    topology's data axis: replica row ``r`` holds a full copy of the
    posting mirrors (``queries[i][j]`` is a :class:`ReplicatedDeviceSet`)
    and processes its contiguous ``B / replicas`` slice of the bucket, so
    a bucket occupies every device without every device seeing every
    query.  B pads up to ``max(replicas, next pow2)`` so the batch axis
    always divides the data axis; padding rows repeat the first query and
    are dropped before results materialize, and a replica whose slice is
    *entirely* padding is never dispatched at all (a 1-query bucket on a
    4-replica topology runs one row, not four).

    The data axis is host-driven, the shard axis shard_map-ped: each row's
    slice is one async dispatch of the row-local pipeline — the 1-D
    z-sharded kernel over the row's submesh (``topology.row_mesh(r)``)
    when ``shards > 1``, the plain single-device kernel on the row's
    anchor otherwise — and every row's handles are collected at ONE
    ``device_get``, so rows overlap in flight.  No collective ever crosses
    the data axis (queries are independent), and within a row the z split
    is communication-free by Theorem 3.7's alignment — driving the data
    axis from the host instead of a single 2-D shard_map costs nothing in
    semantics and avoids GSPMD materializing the stacked batch replicated
    on every row (measured 3-10x slower on CPU meshes).

    Overflow stays per (query, shard): a query whose survivors exceed
    ``capacity_per_shard`` on ANY of its row's shards is re-run as ONE
    enlarged subset pass at the local group count, where overflow is
    impossible — results are bit-identical to the 1-D and host paths in
    every case.  Counters: ``mesh2d_calls`` per bucket pass,
    ``mesh2d_row_dispatches`` per row execution, ``mesh2d_traces`` /
    ``mesh2d_rerun_calls`` as in the ``sharded_*`` family.

    The synchronous composition of :func:`dispatch_mesh2d_batch` +
    :meth:`PendingBatch.collect`.
    """
    return dispatch_mesh2d_batch(
        queries, topology, capacity_per_shard=capacity_per_shard,
        use_pallas=use_pallas,
    ).collect()


# --------------------------------------------------------------------------
# count-only execution: the set-similarity suggestion workload
# --------------------------------------------------------------------------
#
# ``suggest(set_id, k)`` scores one probe set's intersection *cardinality*
# against C candidate sets and keeps the top K — the inner loop of
# set-similarity join.  Cardinality needs none of the point-query
# machinery: no phase-1 filter (every group tuple is counted, there is
# nothing to recover), no survivor compaction, no capacity buffer, and
# therefore NO overflow re-run — each (probe, candidate) pair reduces to
# one int32 and a bucket is one packed (B, C) count matrix.
#
# Exactness without a filter: with all sets partitioned by the same
# permutation g, iterate the G = 2^t_max group tuples of the DEEPER set
# and count its group-g elements present in the other set's aligned group
# ``g >> (t_max - t_min)`` (kernels.count.pair_count).  A common element x
# appears in exactly ONE tuple of the deeper set — the one indexed by its
# full-depth prefix — so summing the per-tuple counts over all G tuples
# counts x exactly once: the per-pair sum IS |probe ∩ candidate|.
#
# Top-K selection runs on device inside the same jit: padded candidate
# slots (the C axis pads to the signature's pow2 ``cands`` tier) are
# masked to -1 via the traced per-query candidate count, and
# ``jax.lax.top_k`` — which breaks ties by LOWEST index — runs over
# candidates the callers order by ascending id, so equal counts
# deterministically prefer the smallest candidate id.  The host merges
# per-bucket top lists by ``(-count, id)``.
#
# Sharding: counts are additive over disjoint z-ranges (Theorem 3.7 —
# each common element lives in exactly one z-range), so the z-sharded
# twin computes per-shard (B, C) partial counts with zero communication
# and sums them outside the shard_map (the only cross-device traffic is
# the B*C count matrix — the analogue of the point path's compact result
# buffers).  Top-K then runs on the summed totals in the same jit.  The
# 2-D path drives replica rows host-side exactly like
# :func:`dispatch_mesh2d_batch`.


def default_k_tier(k: int) -> int:
    """Static top-K selection tier: next power of two, floored at 8.

    Plays the role ``default_capacity`` plays for the point path — the
    requested ``k`` quantizes UP to a tier so nearby k values share one
    compiled executable; the host slices the device's top ``k_tier`` list
    down to the requested k.  Stored in ``ShapeSig.capacity_tier`` for
    suggest plans (the count path has no survivor buffer, so the field is
    free to key the selection width instead)."""
    return 1 << max(3, (int(k) - 1).bit_length())


def _count_block(pv: jnp.ndarray, cv: jnp.ndarray, ts: Tuple[int, int],
                 use_pallas) -> jnp.ndarray:
    """(B, Gp, gp) probe x (B, C, Gc, gc) candidates -> (B, C) counts.

    Shared by the plain jit and the per-shard local block (shapes are then
    the local z-slices; the t-difference shift is shard-invariant because
    equal z-ranges of both sets land on the same shard).  The deeper set
    supplies the iterated groups (counted once each); the shallower set's
    groups are gathered through the prefix-alignment shift — a broadcast
    in disguise, as in :func:`_aligned_images`.
    """
    tp, tc = ts
    B = pv.shape[0]
    C = cv.shape[1]
    if tp >= tc:
        G = pv.shape[1]
        a = jnp.broadcast_to(pv[:, None], (B, C) + pv.shape[1:])
        if tp == tc:
            b = cv
        else:
            idx = jnp.arange(G, dtype=jnp.int32) >> (tp - tc)
            b = cv[:, :, idx]
    else:
        G = cv.shape[2]
        idx = jnp.arange(G, dtype=jnp.int32) >> (tc - tp)
        a = cv
        b = jnp.broadcast_to(pv[:, idx][:, None],
                             (B, C, G, pv.shape[-1]))
    per_tuple = ops.pair_count(a, b, use_pallas)        # (B, C, G)
    return per_tuple.sum(axis=-1, dtype=jnp.int32)


@functools.partial(
    jax.jit,
    static_argnames=("ts", "gmaxes", "k_sel", "use_pallas", "trace_counter"),
)
def _intersect_count_batch(
    probe_vals: Tuple[jnp.ndarray, ...],
    cand_vals: Tuple[Tuple[jnp.ndarray, ...], ...],
    n_cands: jnp.ndarray,
    ts: Tuple[int, int],
    gmaxes: Tuple[int, int],
    k_sel: int,
    use_pallas,
    trace_counter: str = "count_traces",
):
    """One jit execution for a whole same-signature suggest bucket.

    ``probe_vals``: B arrays of (2^{t_p}, gmax_p) int32; ``cand_vals``: B
    tuples of C arrays of (2^{t_c}, gmax_c) int32 — stacked inside the jit
    like the point pipeline.  ``n_cands`` is a traced (B,) int32 of REAL
    candidate counts per row; slots at or past it (C-axis padding repeats
    candidate 0) are masked to count -1 so they can never win top-K and
    the executable never retraces on the fill level.  Returns
    ``(top_counts, top_idx)``, each (B, k_sel) int32 — ``top_idx`` indexes
    the row's candidate list, which callers order by ascending id so
    ``lax.top_k``'s lowest-index tie-break is the smallest-id rule.
    """
    EXEC_COUNTERS[trace_counter] += 1  # python side effect: trace-time only
    pv = jnp.stack(probe_vals)                            # (B, Gp, gp)
    cv = jnp.stack([jnp.stack(row) for row in cand_vals])  # (B, C, Gc, gc)
    counts = _count_block(pv, cv, ts, use_pallas)         # (B, C)
    C = cv.shape[1]
    slot = jnp.arange(C, dtype=jnp.int32)[None, :]
    masked = jnp.where(slot < n_cands[:, None], counts, -1)
    return jax.lax.top_k(masked, k_sel)


@functools.partial(
    jax.jit,
    static_argnames=("mesh", "axis", "ts", "gmaxes", "k_sel", "use_pallas",
                     "trace_counter"),
)
def _intersect_count_sharded_batch(
    probe_vals: Tuple[jnp.ndarray, ...],
    cand_vals: Tuple[Tuple[jnp.ndarray, ...], ...],
    n_cands: jnp.ndarray,
    mesh: Mesh,
    axis: str,
    ts: Tuple[int, int],
    gmaxes: Tuple[int, int],
    k_sel: int,
    use_pallas,
    trace_counter: str = "count_traces",
):
    """The z-sharded twin of :func:`_intersect_count_batch`.

    Each shard computes partial (B, C) counts over its local z-range with
    no communication (counts are additive over disjoint z-ranges); the
    per-shard matrices concatenate to (n_shards, B, C), sum OUTSIDE the
    shard_map (still inside this jit), and top-K runs on the totals.
    Requires both 2^{t_p} and 2^{t_c} to split evenly over the mesh.
    """
    EXEC_COUNTERS[trace_counter] += 1  # python side effect: trace-time only
    pv = jnp.stack(probe_vals)                            # (B, Gp, gp)
    cv = jnp.stack([jnp.stack(row) for row in cand_vals])  # (B, C, Gc, gc)

    def local_fn(lpv, lcv):
        # leading length-1 shard axis so out_specs concatenate the partial
        # count matrices into (n_shards, B, C) without communication
        return _count_block(lpv, lcv, ts, use_pallas)[None]

    fn = jax.shard_map(
        local_fn, mesh=mesh,
        in_specs=(P(None, axis), P(None, None, axis)),
        out_specs=P(axis), check_vma=False,
    )
    counts = fn(pv, cv).sum(axis=0)                       # (B, C)
    C = cv.shape[1]
    slot = jnp.arange(C, dtype=jnp.int32)[None, :]
    masked = jnp.where(slot < n_cands[:, None], counts, -1)
    return jax.lax.top_k(masked, k_sel)


def _count_signature(queries) -> Tuple[Tuple[int, int], Tuple[int, int], int]:
    """Validate a suggest bucket and return (ts, gmaxes, c_tier).

    Every probe must share (t, gmax), every candidate must share (t,
    gmax), and the candidate-axis tier is the pow2 ceiling of the largest
    row's candidate count (matching ``ShapeSig.cands`` for plans bucketed
    by the planner).
    """
    probe0, cands0 = queries[0]
    assert len(cands0) >= 1, "suggest rows need at least one candidate"
    tp, gp = probe0.t, probe0.gmax
    tc, gc = cands0[0].t, cands0[0].gmax
    max_c = 0
    for probe, cands in queries:
        assert (probe.t, probe.gmax) == (tp, gp), (
            "bucket mixes probe shapes")
        assert len(cands) >= 1, "suggest rows need at least one candidate"
        for c in cands:
            assert (c.t, c.gmax) == (tc, gc), "bucket mixes candidate shapes"
        max_c = max(max_c, len(cands))
    c_tier = 1 << (max_c - 1).bit_length()
    return (tp, tc), (gp, gc), c_tier


def _pack_count_rows(queries, rows: List[int], c_tier: int):
    """Stack bucket rows into the count jit's pytree inputs: pad each
    row's candidate list to ``c_tier`` by repeating candidate 0 (masked
    off by ``n_cands``), B-pad by repeating row 0 (dropped at collect)."""
    probe_vals = tuple(queries[i][0].vals for i in rows)
    cand_vals = tuple(
        tuple((queries[i][1] + [queries[i][1][0]]
               * (c_tier - len(queries[i][1])))[j].vals
              for j in range(c_tier))
        for i in rows
    )
    n_cands = jnp.asarray([len(queries[i][1]) for i in rows], jnp.int32)
    return probe_vals, cand_vals, n_cands


def _collect_count(handles, queries, k_sel: int, extra_stats: Dict,
                   passes: List[PassRecord], t_issue: float, row_of=None):
    """Shared collect for the count paths: one transfer, no re-run loop.

    ``row_of`` maps query index -> (handle key, local row) for the 2-D
    host-driven layout; None means a single handle covering all rows."""
    fetched = _fetch_pass(passes, handles, len(queries), k_sel, t_issue)
    results: List[Tuple[np.ndarray, Dict]] = []
    for qi, (probe, cands) in enumerate(queries):
        if row_of is None:
            top_counts, top_idx = fetched
            row = qi
        else:
            key, row = row_of(qi)
            top_counts, top_idx = fetched[key]
        pairs = np.stack(
            [top_idx[row], top_counts[row]], axis=1).astype(np.int32)
        stats = {
            "n_cands": len(cands),
            "k_sel": k_sel,
            "batch_size": len(queries),
            **extra_stats,
        }
        if row_of is not None:
            stats["replica"] = key
        results.append((pairs, stats))
    return results


def dispatch_count_batch(
    queries: Sequence[Tuple[DeviceSet, Sequence[DeviceSet]]],
    k: int,
    use_pallas="auto",
) -> PendingBatch:
    """Issue one count-only suggest bucket without blocking.

    ``queries[i]`` is ``(probe, candidates)`` — candidates ordered by
    ascending id by the caller (the tie-break contract).  ``k`` is the
    selection tier (``ShapeSig.capacity_tier`` for planned buckets); the
    device returns each row's top ``min(k, c_tier)`` (idx, count) pairs
    and the host keeps what it needs.  ONE pass per bucket — the count
    path has no overflow re-run by construction.  Counters:
    ``count_calls`` per pass, ``count_traces`` per compile.
    """
    if not len(queries):
        return PendingBatch(n_queries=0, _collect=lambda: [])
    queries = [(p, list(c)) for p, c in queries]
    ts, gmaxes, c_tier = _count_signature(queries)
    k_sel = min(int(k), c_tier)
    b_tier = 1 << (len(queries) - 1).bit_length()
    rows = list(range(len(queries))) + [0] * (b_tier - len(queries))
    probe_vals, cand_vals, n_cands = _pack_count_rows(queries, rows, c_tier)
    passes: List[PassRecord] = []
    t_issue = time.perf_counter()
    EXEC_COUNTERS["count_calls"] += 1
    handles = _intersect_count_batch(
        probe_vals, cand_vals, n_cands, ts, gmaxes, k_sel, use_pallas)
    extra = {"c_tier": c_tier, "group_tuples": 1 << max(ts)}
    return PendingBatch(
        n_queries=len(queries), handles=handles,
        _collect=lambda: _collect_count(handles, queries, k_sel, extra,
                                        passes, t_issue),
        passes=passes,
    )


def intersect_count_batch(
    queries: Sequence[Tuple[DeviceSet, Sequence[DeviceSet]]],
    k: int,
    use_pallas="auto",
) -> List[Tuple[np.ndarray, Dict]]:
    """Count-only suggest bucket, synchronously: B (probe, candidates)
    rows -> per row a (k_sel, 2) int32 array of (candidate index, count)
    pairs, best-first under the ``(-count, smallest id)`` order, plus
    stats.  Padded / past-the-end slots carry count -1; the serving layer
    drops counts < 1 (a zero-overlap candidate is not a suggestion).  The
    synchronous composition of :func:`dispatch_count_batch` +
    :meth:`PendingBatch.collect`."""
    return dispatch_count_batch(queries, k, use_pallas=use_pallas).collect()


def dispatch_count_sharded_batch(
    queries: Sequence[Tuple[DeviceSet, Sequence[DeviceSet]]],
    k: int,
    mesh: Mesh,
    axis: str = SHARD_AXIS,
    use_pallas="auto",
) -> PendingBatch:
    """Issue one suggest bucket z-sharded over ``mesh`` without blocking.

    Same contract as :func:`dispatch_count_batch`; both the probe's and
    the candidates' z axes must split evenly over the mesh (the planner's
    routing rule guarantees it for planned buckets).  Pass z-sharded
    mirrors to avoid a per-call reshard.
    """
    if not len(queries):
        return PendingBatch(n_queries=0, _collect=lambda: [])
    n_shards = mesh.shape[axis]
    queries = [(p, list(c)) for p, c in queries]
    ts, gmaxes, c_tier = _count_signature(queries)
    assert (1 << ts[0]) % n_shards == 0 and (1 << ts[1]) % n_shards == 0, (
        f"both z axes (t={ts}) must split over {n_shards} shards"
    )
    k_sel = min(int(k), c_tier)
    b_tier = 1 << (len(queries) - 1).bit_length()
    rows = list(range(len(queries))) + [0] * (b_tier - len(queries))
    probe_vals, cand_vals, n_cands = _pack_count_rows(queries, rows, c_tier)
    passes: List[PassRecord] = []
    t_issue = time.perf_counter()
    EXEC_COUNTERS["count_calls"] += 1
    handles = _intersect_count_sharded_batch(
        probe_vals, cand_vals, n_cands, mesh, axis, ts, gmaxes, k_sel,
        use_pallas)
    extra = {"c_tier": c_tier, "group_tuples": 1 << max(ts),
             "n_shards": n_shards}
    return PendingBatch(
        n_queries=len(queries), handles=handles,
        _collect=lambda: _collect_count(handles, queries, k_sel, extra,
                                        passes, t_issue),
        passes=passes,
    )


def intersect_count_sharded_batch(
    queries: Sequence[Tuple[DeviceSet, Sequence[DeviceSet]]],
    k: int,
    mesh: Mesh,
    axis: str = SHARD_AXIS,
    use_pallas="auto",
) -> List[Tuple[np.ndarray, Dict]]:
    """Synchronous composition of :func:`dispatch_count_sharded_batch` +
    :meth:`PendingBatch.collect` — bit-identical to the plain count path
    (counts are additive over z-ranges; top-K runs on the exact totals)."""
    return dispatch_count_sharded_batch(
        queries, k, mesh, axis=axis, use_pallas=use_pallas).collect()


def dispatch_count_mesh2d_batch(
    queries: Sequence[Tuple[DeviceSet, Sequence[DeviceSet]]],
    k: int,
    topology,
    use_pallas="auto",
) -> PendingBatch:
    """Issue one suggest bucket over a 2-D ``(data, shard)`` topology.

    The count twin of :func:`dispatch_mesh2d_batch`: the batch axis is cut
    into contiguous equal slices driven host-side (one async row dispatch
    each — the z-sharded count jit on the row's submesh, or the plain
    count jit when ``shards == 1``), rows overlap in flight, and one
    ``device_get`` collects everything.  ``queries[i]`` resolves per row:
    probes/candidates are :class:`ReplicatedDeviceSet` mirrors.  Counters:
    ``count_calls`` per row dispatch (each row is one jit execution),
    ``mesh2d_row_dispatches`` per row as in the point path.
    """
    if not len(queries):
        return PendingBatch(n_queries=0, _collect=lambda: [])
    n_replicas = topology.replicas
    n_shards = topology.shards
    queries = [(p, list(c)) for p, c in queries]
    ts = (queries[0][0].t, queries[0][1][0].t)
    if n_shards > 1:
        assert ((1 << ts[0]) % n_shards == 0
                and (1 << ts[1]) % n_shards == 0), (
            f"both z axes (t={ts}) must split over {n_shards} shards"
        )
    b_tier = max(n_replicas, 1 << (len(queries) - 1).bit_length())
    rows = list(range(len(queries))) + [0] * (b_tier - len(queries))
    slice_len = b_tier // n_replicas
    c_tier = 1 << (max(len(c) for _, c in queries) - 1).bit_length()
    k_sel = min(int(k), c_tier)
    passes: List[PassRecord] = []
    t_issue = time.perf_counter()
    handles = {}
    for rr in range(n_replicas):
        if rr * slice_len >= len(queries):
            continue  # slice is pure padding: nothing real to compute
        chunk = rows[rr * slice_len:(rr + 1) * slice_len]
        row_queries = [
            (queries[i][0].row(rr), [c.row(rr) for c in queries[i][1]])
            for i in chunk
        ]
        tsr, gmaxes, _ = _count_signature(row_queries)
        probe_vals, cand_vals, n_cands = _pack_count_rows(
            row_queries, list(range(len(chunk))), c_tier)
        EXEC_COUNTERS["count_calls"] += 1
        EXEC_COUNTERS["mesh2d_row_dispatches"] += 1
        if n_shards > 1:
            handles[rr] = _intersect_count_sharded_batch(
                probe_vals, cand_vals, n_cands, topology.row_mesh(rr),
                topology.shard_axis, tsr, gmaxes, k_sel, use_pallas)
        else:
            handles[rr] = _intersect_count_batch(
                probe_vals, cand_vals, n_cands, tsr, gmaxes, k_sel,
                use_pallas)

    def row_of(qi: int) -> Tuple[int, int]:
        return qi // slice_len, qi % slice_len

    extra = {"c_tier": c_tier, "group_tuples": 1 << max(ts),
             "n_shards": n_shards, "n_replicas": n_replicas}
    return PendingBatch(
        n_queries=len(queries), handles=handles,
        _collect=lambda: _collect_count(handles, queries, k_sel, extra,
                                        passes, t_issue, row_of=row_of),
        passes=passes,
    )


def intersect_count_mesh2d_batch(
    queries: Sequence[Tuple[DeviceSet, Sequence[DeviceSet]]],
    k: int,
    topology,
    use_pallas="auto",
) -> List[Tuple[np.ndarray, Dict]]:
    """Synchronous composition of :func:`dispatch_count_mesh2d_batch` +
    :meth:`PendingBatch.collect`."""
    return dispatch_count_mesh2d_batch(
        queries, k, topology, use_pallas=use_pallas).collect()


# --------------------------------------------------------------------------
# boolean expression evaluation: ∪ / ∩ / ∖ DAGs over dense value buffers
# --------------------------------------------------------------------------
#
# Non-flat expressions (anything but a pure conjunction of terms — those
# keep the bitmap-filter + group-match pipeline above, byte-identical)
# evaluate on **dense value buffers**: each leaf's (2^t, gmax) z-prefix
# group layout flattens to one sorted uint32 row per query
# (kernels.setops.densify — the int32 -1 padding bitcasts to the
# 0xFFFFFFFF sentinel, which sorts last), and every DAG node is a
# sort-merge pass over its children's buffers, bottom-up, entirely
# on-device inside ONE jit per bucket.  There is no bitmap/group phase
# for mixed nodes because intermediates (a∪b, …) have no precomputed
# filter images — the dense representation is the paper's structures'
# "value view", and Bille–Pagh–Pagh-style evaluation over it keeps every
# node a linear merge.
#
# The overflow contract is the flat pipeline's, verbatim: every
# *composite* node writes into a static buffer of width
# ``min(capacity, natural)`` (natural = what its children could supply);
# a per-query flag records any node whose true count exceeded its
# buffer, and flagged queries are re-run ONCE at ``capacity = total leaf
# width``, where no node can overflow — results are bit-identical to the
# numpy oracle in every case.  Sharding: all leaves share the
# permutation g, so ∪/∩/∖ distribute over z-ranges — each shard
# evaluates the whole DAG on its local slices with NO communication
# (the expression twin of Theorem 3.7's alignment), overflow stays per
# (query, shard), and per-shard result segments concatenate.
#
# Subexpression sharing: the evaluator also emits the value buffer of
# every composite proper subexpression (postorder), which the serving
# layer stores in the result cache keyed on the canonical subexpression
# — a later query containing the same subtree resolves host-side.


def _expr_signature(row: Sequence[DeviceSet]
                    ) -> Tuple[Tuple[int, ...], Tuple[int, ...]]:
    """Leaf signature in TRAVERSAL order — expression rows are ordered by
    the expression's leaf walk (``exec.expr.leaf_terms``), never re-sorted
    (position encodes which leaf of the DAG a set feeds)."""
    return tuple(s.t for s in row), tuple(s.gmax for s in row)


def expr_total_width(ts: Tuple[int, ...], gmaxes: Tuple[int, ...]) -> int:
    """Total dense width of an expression's leaves — the capacity at which
    no node can overflow (every result value originates from some leaf)."""
    return sum((1 << t) * g for t, g in zip(ts, gmaxes))


def default_expr_capacity(ts: Tuple[int, ...],
                          gmaxes: Tuple[int, ...]) -> int:
    """Survivor-buffer tier for expression nodes: total/4 with a floor,
    rounded to the power-of-two lattice — the expression analogue of
    :func:`default_capacity` (union nodes routinely carry more values
    than an intersection's survivors, so the prior is deliberately
    generous; the adaptive ``CapacityModel`` refines it per shape from
    observed node counts)."""
    total = expr_total_width(ts, gmaxes)
    tier = 1 << max(0, (total - 1).bit_length())
    return max(64, tier // 4)


def default_expr_capacity_per_shard(ts: Tuple[int, ...],
                                    gmaxes: Tuple[int, ...],
                                    n_shards: int,
                                    capacity: Optional[int] = None) -> int:
    """Per-shard node-buffer tier for the sharded expression pipeline —
    the expression analogue of :func:`default_capacity_per_shard`."""
    local_total = expr_total_width(ts, gmaxes) // n_shards
    whole = (default_expr_capacity(ts, gmaxes) if capacity is None
             else int(capacity))
    return min(local_total, max(16, whole // n_shards))


def _count_expr_subs(eshape) -> int:
    """Number of composite proper subexpressions (postorder emission count
    of `_eval_expr_block`) — static in the shape, so shard_map out_specs
    can size the sub-buffer pytree."""
    if eshape == "T":
        return 0
    n = 0
    for child in eshape[1:]:
        n += _count_expr_subs(child)
        if child != "T":
            n += 1
    return n


def _eval_expr_block(vals, eshape, capacity: int):
    """Evaluate one expression DAG over stacked leaf arrays, bottom-up.

    ``vals[i]``: (B, 2^t_i[, /S], gmax_i) int32 leaf arrays in traversal
    order.  Returns ``(root, r, max_count, overflow, subs)``: the root's
    sorted sentinel-padded (B, W_root) uint32 buffer, its true count, the
    max true count over all composite nodes (the adaptive model's
    survivor statistic), the per-query any-node-truncated flag, and the
    postorder tuple of composite proper-subexpression buffers.
    """
    dense = [setops.densify(v) for v in vals]
    next_leaf = [0]
    subs: List[jnp.ndarray] = []
    zero = jnp.zeros(dense[0].shape[0], dtype=jnp.int32)
    state = {"overflow": zero > 0, "max_count": zero}

    def node(shape, root: bool):
        if shape == "T":
            buf = dense[next_leaf[0]]
            next_leaf[0] += 1
            return buf
        op = shape[0]
        if op == "-":
            left = node(shape[1], False)
            right = node(shape[2], False)
            width = min(capacity, left.shape[1])
            out, count = setops.diff_pass(left, right, width)
        elif op == "|":
            bufs = [node(s, False) for s in shape[1:]]
            width = min(capacity, sum(b.shape[1] for b in bufs))
            out, count = setops.union_pass(bufs, width)
        else:
            bufs = [node(s, False) for s in shape[1:]]
            width = min(capacity, bufs[0].shape[1])
            out, count = setops.intersect_pass(bufs, width)
        state["overflow"] = state["overflow"] | (count > out.shape[1])
        state["max_count"] = jnp.maximum(state["max_count"], count)
        if not root:
            subs.append(out)
        else:
            state["r"] = count
        return out

    root = node(eshape, True)
    return (root, state["r"], state["max_count"], state["overflow"],
            tuple(subs))


@functools.partial(
    jax.jit,
    static_argnames=("eshape", "ts", "gmaxes", "capacity", "trace_counter"),
)
def _eval_expr_batch(
    vals: Tuple[Tuple[jnp.ndarray, ...], ...],
    eshape,
    ts: Tuple[int, ...],
    gmaxes: Tuple[int, ...],
    capacity: int,
    trace_counter: str = "expr_traces",
):
    """One jit execution for a whole same-shape bucket of B expression
    queries — the expression twin of :func:`_intersect_k_batch` (same
    in-jit stacking, same static-shape discipline; ``eshape`` + ``ts`` +
    ``gmaxes`` + ``capacity`` fully determine every buffer width)."""
    EXEC_COUNTERS[trace_counter] += 1  # python side effect: trace-time only
    vals = tuple(jnp.stack(v) for v in vals)
    return _eval_expr_block(vals, eshape, capacity)


@functools.partial(
    jax.jit,
    static_argnames=("mesh", "axis", "eshape", "ts", "gmaxes",
                     "capacity_per_shard", "trace_counter"),
)
def _eval_expr_sharded_batch(
    vals: Tuple[Tuple[jnp.ndarray, ...], ...],
    mesh: Mesh,
    axis: str,
    eshape,
    ts: Tuple[int, ...],
    gmaxes: Tuple[int, ...],
    capacity_per_shard: int,
    trace_counter: str = "expr_traces",
):
    """The z-sharded expression evaluator: every shard runs the whole DAG
    on its local z-slices (``g`` aligns all leaves, so ∪/∩/∖ distribute
    over z-ranges with no communication), per-shard node buffers
    concatenate along the width axis, and the per-(query, shard) flags
    drive the host-side enlarged re-run exactly as in
    :func:`_intersect_k_sharded_batch`."""
    EXEC_COUNTERS[trace_counter] += 1  # python side effect: trace-time only
    vals = tuple(jnp.stack(v) for v in vals)
    n_subs = _count_expr_subs(eshape)

    def local_fn(*lvals):
        root, r, max_count, overflow, subs = _eval_expr_block(
            lvals, eshape, capacity_per_shard)
        return root, r[None], max_count[None], overflow[None], subs

    in_specs = tuple([P(None, axis)] * len(ts))
    out_specs = (P(None, axis), P(axis), P(axis), P(axis),
                 tuple([P(None, axis)] * n_subs))
    fn = jax.shard_map(local_fn, mesh=mesh, in_specs=in_specs,
                       out_specs=out_specs, check_vma=False)
    return fn(*vals)


_EXPR_SENTINEL = np.uint32(0xFFFFFFFF)


def _compact_u32(row: np.ndarray) -> np.ndarray:
    """Sentinel-padded uint32 buffer (any per-shard segment order) ->
    sorted value array, the serving result/value format."""
    flat = row.ravel()
    return np.sort(flat[flat != _EXPR_SENTINEL])


def dispatch_expr_batch(
    queries: Sequence[Sequence[DeviceSet]],
    eshape,
    capacity: Optional[int] = None,
    sub_keys: Optional[Sequence[Sequence]] = None,
) -> PendingBatch:
    """Issue the first pass of a same-shape expression bucket.

    ``queries[i]`` is query i's leaf DeviceSets in the expression's
    traversal order (NOT (t, n)-sorted — position encodes DAG wiring);
    all queries must share ``eshape`` and the leaf signature.
    ``sub_keys[i]`` (optional) are query i's canonical subexpression
    cache keys, postorder — when given, collected stats carry
    ``"subexprs": [(key, sorted values), …]`` for the serving layer to
    store.  Counters: ``expr_calls`` per pass, ``expr_rerun_calls`` per
    overflow pass, ``expr_traces`` per compile.
    """
    if not len(queries):
        return PendingBatch(n_queries=0, _collect=lambda: [])
    ordered = [list(q) for q in queries]
    ts, gmaxes = _expr_signature(ordered[0])
    for q in ordered[1:]:
        assert _expr_signature(q) == (ts, gmaxes), (
            "bucket mixes expression leaf signatures")
    total = expr_total_width(ts, gmaxes)

    def issue(active: List[int], cap: int):
        b_tier = 1 << (len(active) - 1).bit_length()
        rows = active + [active[0]] * (b_tier - len(active))
        vals = tuple(
            tuple(ordered[i][j].vals for i in rows) for j in range(len(ts))
        )
        EXEC_COUNTERS["expr_calls"] += 1
        return _eval_expr_batch(vals, eshape, ts, gmaxes, cap)

    first_active = list(range(len(ordered)))
    first_cap = min(capacity or default_expr_capacity(ts, gmaxes), total)
    passes: List[PassRecord] = []
    first_issue = time.perf_counter()
    first_handles = issue(first_active, first_cap)

    def collect() -> List[Tuple[np.ndarray, Dict]]:
        results: List[Optional[Tuple[np.ndarray, Dict]]] = [None] * len(ordered)
        active, cap, handles = first_active, first_cap, first_handles
        t_issue = first_issue
        while True:
            root_h, r_h, maxc_h, over_h, subs_h = _fetch_pass(
                passes, handles, len(active), cap, t_issue)
            rerun = []
            for row, qi in enumerate(active):
                if over_h[row]:
                    rerun.append(qi)
                    continue
                stats = {
                    "expr_width": total,
                    "tuples_survived": int(maxc_h[row]),
                    "capacity": cap,
                    "r": int(r_h[row]),
                    "batch_size": len(active),
                }
                if sub_keys is not None:
                    stats["subexprs"] = [
                        (key, _compact_u32(sub[row]))
                        for key, sub in zip(sub_keys[qi], subs_h)
                    ]
                results[qi] = (_compact_u32(root_h[row]), stats)
            if not rerun:
                return results  # type: ignore[return-value]
            active = rerun
            cap = total  # rare path: ONE re-run where no node can overflow
            EXEC_COUNTERS["expr_rerun_calls"] += 1
            t_issue = time.perf_counter()
            handles = issue(active, cap)

    return PendingBatch(n_queries=len(ordered), handles=first_handles,
                        _collect=collect, passes=passes)


def dispatch_expr_sharded_batch(
    queries: Sequence[Sequence[DeviceSet]],
    eshape,
    mesh: Mesh,
    axis: str = SHARD_AXIS,
    capacity_per_shard: Optional[int] = None,
    sub_keys: Optional[Sequence[Sequence]] = None,
) -> PendingBatch:
    """Issue the first z-sharded pass of an expression bucket — the
    expression twin of :func:`dispatch_sharded_batch` (same per-(query,
    shard) overflow + single enlarged re-run at the local total width).
    Pass z-sharded leaf mirrors; every leaf must split over the mesh."""
    if not len(queries):
        return PendingBatch(n_queries=0, _collect=lambda: [])
    n_shards = mesh.shape[axis]
    ordered = [list(q) for q in queries]
    ts, gmaxes = _expr_signature(ordered[0])
    for q in ordered[1:]:
        assert _expr_signature(q) == (ts, gmaxes), (
            "bucket mixes expression leaf signatures")
    assert all((1 << t) % n_shards == 0 for t in ts), (
        f"every leaf must split over {n_shards} shards")
    total = expr_total_width(ts, gmaxes)
    local_total = total // n_shards

    def issue(active: List[int], cap: int):
        b_tier = 1 << (len(active) - 1).bit_length()
        rows = active + [active[0]] * (b_tier - len(active))
        vals = tuple(
            tuple(ordered[i][j].vals for i in rows) for j in range(len(ts))
        )
        EXEC_COUNTERS["expr_calls"] += 1
        return _eval_expr_sharded_batch(vals, mesh, axis, eshape, ts,
                                        gmaxes, cap)

    first_active = list(range(len(ordered)))
    first_cap = min(
        capacity_per_shard
        or default_expr_capacity_per_shard(ts, gmaxes, n_shards),
        local_total,
    )
    passes: List[PassRecord] = []
    first_issue = time.perf_counter()
    first_handles = issue(first_active, first_cap)

    def collect() -> List[Tuple[np.ndarray, Dict]]:
        results: List[Optional[Tuple[np.ndarray, Dict]]] = [None] * len(ordered)
        active, cap, handles = first_active, first_cap, first_handles
        t_issue = first_issue
        while True:
            root_h, r_h, maxc_h, over_h, subs_h = _fetch_pass(
                passes, handles, len(active), cap, t_issue)
            rerun = []
            for row, qi in enumerate(active):
                if over_h[:, row].any():
                    rerun.append(qi)
                    continue
                stats = {
                    "expr_width": total,
                    "tuples_survived": int(maxc_h[:, row].sum()),
                    "max_shard_survivors": int(maxc_h[:, row].max()),
                    "capacity_per_shard": cap,
                    "n_shards": n_shards,
                    "r": int(r_h[:, row].sum()),
                    "batch_size": len(active),
                }
                if sub_keys is not None:
                    stats["subexprs"] = [
                        (key, _compact_u32(sub[row]))
                        for key, sub in zip(sub_keys[qi], subs_h)
                    ]
                results[qi] = (_compact_u32(root_h[row]), stats)
            if not rerun:
                return results  # type: ignore[return-value]
            active = rerun
            cap = local_total  # one re-run at local total: no overflow
            EXEC_COUNTERS["expr_rerun_calls"] += 1
            t_issue = time.perf_counter()
            handles = issue(active, cap)

    return PendingBatch(n_queries=len(ordered), handles=first_handles,
                        _collect=collect, passes=passes)


def dispatch_expr_mesh2d_batch(
    queries: Sequence[Sequence[ReplicatedDeviceSet]],
    eshape,
    topology,
    capacity_per_shard: Optional[int] = None,
    sub_keys: Optional[Sequence[Sequence]] = None,
) -> PendingBatch:
    """Issue the first 2-D (data x shard) pass of an expression bucket —
    the expression twin of :func:`dispatch_mesh2d_batch`: the batch axis
    splits over host-driven replica rows, each row runs the 1-D sharded
    (or plain) expression evaluator on its slice, one collection point."""
    if not len(queries):
        return PendingBatch(n_queries=0, _collect=lambda: [])
    n_replicas = topology.replicas
    n_shards = topology.shards
    assert n_replicas & (n_replicas - 1) == 0, (
        "data axis must be a power of two (batch tiers are pow2)")
    ordered = [list(q) for q in queries]
    ts, gmaxes = _expr_signature(ordered[0])
    for q in ordered[1:]:
        assert _expr_signature(q) == (ts, gmaxes), (
            "bucket mixes expression leaf signatures")
    assert all((1 << t) % n_shards == 0 for t in ts), (
        f"every leaf must split over {n_shards} shards")
    total = expr_total_width(ts, gmaxes)
    local_total = total // n_shards

    def issue(active: List[int], cap: int):
        b_tier = max(n_replicas, 1 << (len(active) - 1).bit_length())
        rows = active + [active[0]] * (b_tier - len(active))
        slice_len = b_tier // n_replicas
        EXEC_COUNTERS["expr_calls"] += 1
        handles = {}
        for rr in range(n_replicas):
            if rr * slice_len >= len(active):
                continue  # slice is pure padding: nothing real to compute
            chunk = rows[rr * slice_len:(rr + 1) * slice_len]
            vals = tuple(
                tuple(ordered[i][j].row(rr).vals for i in chunk)
                for j in range(len(ts))
            )
            if n_shards > 1:
                out = _eval_expr_sharded_batch(
                    vals, topology.row_mesh(rr), topology.shard_axis,
                    eshape, ts, gmaxes, cap)
            else:
                root, r, maxc, over, subs = _eval_expr_batch(
                    vals, eshape, ts, gmaxes, cap)
                out = (root, r[None], maxc[None], over[None], subs)
            handles[rr] = out
        return handles, slice_len

    first_active = list(range(len(ordered)))
    first_cap = min(
        capacity_per_shard
        or default_expr_capacity_per_shard(ts, gmaxes, n_shards),
        local_total,
    )
    passes: List[PassRecord] = []
    first_issue = time.perf_counter()
    first_handles, first_slice_len = issue(first_active, first_cap)

    def collect() -> List[Tuple[np.ndarray, Dict]]:
        results: List[Optional[Tuple[np.ndarray, Dict]]] = [None] * len(ordered)
        active, cap = first_active, first_cap
        t_issue = first_issue
        handles, slice_len = first_handles, first_slice_len
        while True:
            fetched = _fetch_pass(passes, handles, len(active), cap,
                                  t_issue)
            rerun = []
            for rr, (root_h, r_h, maxc_h, over_h, subs_h) in fetched.items():
                chunk_start = rr * slice_len
                for local_row in range(slice_len):
                    pos = chunk_start + local_row
                    if pos >= len(active):
                        continue  # padding rows repeat query active[0]
                    qi = active[pos]
                    if over_h[:, local_row].any():
                        rerun.append(qi)
                        continue
                    stats = {
                        "expr_width": total,
                        "tuples_survived": int(maxc_h[:, local_row].sum()),
                        "max_shard_survivors": int(maxc_h[:, local_row].max()),
                        "capacity_per_shard": cap,
                        "n_shards": n_shards,
                        "n_replicas": n_replicas,
                        "replica": rr,
                        "r": int(r_h[:, local_row].sum()),
                        "batch_size": len(active),
                    }
                    if sub_keys is not None:
                        stats["subexprs"] = [
                            (key, _compact_u32(sub[local_row]))
                            for key, sub in zip(sub_keys[qi], subs_h)
                        ]
                    results[qi] = (_compact_u32(root_h[local_row]), stats)
            if not rerun:
                return results  # type: ignore[return-value]
            active = rerun
            cap = local_total  # one re-run at local total: no overflow
            EXEC_COUNTERS["expr_rerun_calls"] += 1
            t_issue = time.perf_counter()
            handles, slice_len = issue(active, cap)

    return PendingBatch(n_queries=len(ordered), handles=first_handles,
                        _collect=collect, passes=passes)


def intersect_expr_batch(queries, eshape, capacity=None, sub_keys=None):
    """Synchronous expression bucket execution (dispatch + collect)."""
    return dispatch_expr_batch(
        queries, eshape, capacity=capacity, sub_keys=sub_keys).collect()


def intersect_expr_sharded_batch(queries, eshape, mesh, axis=SHARD_AXIS,
                                 capacity_per_shard=None, sub_keys=None):
    """Synchronous z-sharded expression bucket execution."""
    return dispatch_expr_sharded_batch(
        queries, eshape, mesh, axis=axis,
        capacity_per_shard=capacity_per_shard, sub_keys=sub_keys).collect()


def intersect_expr_mesh2d_batch(queries, eshape, topology,
                                capacity_per_shard=None, sub_keys=None):
    """Synchronous 2-D expression bucket execution."""
    return dispatch_expr_mesh2d_batch(
        queries, eshape, topology, capacity_per_shard=capacity_per_shard,
        sub_keys=sub_keys).collect()


class BatchedEngine:
    """Corpus-level engine: name -> DeviceSet, query bucketing, jit reuse.

    With a ``mesh`` (1-D, axis ``shard_axis``), :meth:`add` also builds a
    z-sharded mirror of every shardable set at index time and the planner
    routes huge-G queries (``2^t_k >= shard_min_g``) through
    :func:`intersect_sharded_batch` — small queries stay single-device,
    where the shard_map overhead would dominate.  Mutation hooks
    (:meth:`on_mutate`) fire on every :meth:`add` so owners of derived
    state — notably the serving layer's result cache — can invalidate.

    With a ``topology`` (2-D ``exec.topology.Topology``; exclusive with
    ``mesh``), :meth:`add` builds the 2-D mirrors instead — one mirror per
    replica row, z-partitioned over the row's submesh (replication over
    the data axis) — and the planner routes huge-G queries through
    :func:`intersect_mesh2d_batch` while small-query buckets are
    dispatched to the least-loaded replica by the topology's balancer,
    against per-row plain mirrors built lazily on first dispatch.
    """

    def __init__(self, use_pallas="auto", mesh: Optional[Mesh] = None,
                 shard_axis: str = SHARD_AXIS, shard_min_g: int = SHARD_MIN_G,
                 topology=None):
        assert mesh is None or topology is None, (
            "pass a 1-D mesh OR a 2-D topology, not both"
        )
        self.sets: Dict[str, DeviceSet] = {}
        self.sharded_sets: Dict[str, DeviceSet] = {}
        self.use_pallas = use_pallas
        self.mesh = mesh
        self.topology = topology
        self.shard_axis = (topology.shard_axis if topology is not None
                           else shard_axis)
        self.shard_min_g = shard_min_g
        # one plain-mirror dict per replica row (topology only; empty when
        # replicas == 1, where balancer dispatch degenerates to the default
        # single-device path over `sets`)
        self.replica_sets: List[Dict[str, DeviceSet]] = [
            {} for _ in range(topology.replicas)
        ] if topology is not None and topology.replicas > 1 else []
        self.generation = 0
        self._mutation_hooks: List = []

    @property
    def n_shards(self) -> int:
        if self.topology is not None:
            return self.topology.shards
        return self.mesh.shape[self.shard_axis] if self.mesh is not None else 1

    @property
    def n_replicas(self) -> int:
        return self.topology.replicas if self.topology is not None else 1

    def on_mutate(self, hook) -> None:
        """Register a zero-arg callback fired after every index mutation."""
        self._mutation_hooks.append(hook)

    def add(self, name: str, idx: PrefixIndex) -> None:
        ds = DeviceSet.from_host(idx)
        self.sets[name] = ds
        if self.topology is not None:
            # topology mirrors are built lazily on first use
            # (get_replica_set / get_mesh_set) — eagerly replicating every
            # set on every row would multiply device memory for the whole
            # index by the replica count at build time, when only the
            # terms that actually dispatch need row mirrors.  A replaced
            # term must drop its stale lazy mirrors, though.
            for mirrors in self.replica_sets:
                mirrors.pop(name, None)
            self.sharded_sets.pop(name, None)
        elif self.mesh is not None and ds.shardable(self.n_shards):
            self.sharded_sets[name] = ds.shard(self.mesh, self.shard_axis)
        self.generation += 1
        for hook in self._mutation_hooks:
            hook()

    def query(self, names: Sequence[str], capacity: Optional[int] = None):
        dsets = [self.sets[n] for n in names]
        return intersect_device(dsets, capacity=capacity, use_pallas=self.use_pallas)

    def query_many(self, queries: Sequence[Sequence[str]]):
        """Plan -> bucket by shape signature -> one jit execution per bucket
        -> scatter back in request order.  Returns [(values, stats), ...].
        With a mesh attached, huge-G buckets run z-sharded; with a 2-D
        topology they run on the full data x shard mesh and small buckets
        spread over the replicas."""
        from ..exec.batch import execute_name_queries

        return execute_name_queries(
            self.sets, queries, use_pallas=self.use_pallas, mesh=self.mesh,
            shard_axis=self.shard_axis, shard_min_g=self.shard_min_g,
            get_sharded_set=self.get_mesh_set, topology=self.topology,
            get_replica_set=self.get_replica_set,
        )

    def get_replica_set(self, r: int, name: str) -> DeviceSet:
        """Resolve ``name`` to replica row ``r``'s plain mirror, building
        it on first use (lazily: only terms that actually dispatch to a
        replica pay the per-row copy).  Falls back to the default mirror
        when the topology has a single replica.  Benign under the serving
        layer's concurrency: all balancer dispatch happens under the
        engines' execution lock, and a racing duplicate ``place`` of the
        same set is just a redundant copy, not a correctness hazard."""
        if not self.replica_sets:
            return self.sets[name]
        mirrors = self.replica_sets[r]
        if name not in mirrors:
            mirrors[name] = self.sets[name].place(
                self.topology.replica_device(r))
        return mirrors[name]

    def get_mesh_set(self, name: str):
        """Resolve ``name`` to its mesh mirror: the 1-D z-sharded mirror
        (``mesh=`` engines, built eagerly at :meth:`add`) or the 2-D
        :class:`ReplicatedDeviceSet` (topology engines, built lazily here
        on first mesh dispatch — one z-sharded mirror per replica row, or
        the rows' plain anchor mirrors when ``shards == 1``).  The same
        concurrency argument as :meth:`get_replica_set` applies."""
        if self.topology is None:
            return self.sharded_sets[name]
        if name not in self.sharded_sets:
            ds = self.sets[name]
            assert ds.shardable(self.n_shards), (
                f"{name!r}: 2^{ds.t} z-groups do not split over "
                f"{self.n_shards} shards (the planner never mesh-routes "
                "misaligned sets)"
            )
            if self.n_shards > 1:
                rows = tuple(
                    ds.shard(self.topology.row_mesh(r), self.shard_axis)
                    for r in range(self.n_replicas))
            else:
                rows = tuple(self.get_replica_set(r, name)
                             for r in range(self.n_replicas))
            self.sharded_sets[name] = ReplicatedDeviceSet(rows)
        return self.sharded_sets[name]

    def warm(self, sample_queries: Sequence[Sequence[str]], top_k: int = 8,
             b_tiers: Sequence[int] = (1,)):
        """Compile-cache warming from a name-keyed sample workload
        (index-build time).  Plans the sample (with this engine's sharded
        routing, so sharded signatures warm sharded executables) and
        delegates the policy to :func:`warm_from_plans`.  Returns the
        warmed :class:`~repro.exec.plan.ShapeSig`\\ s, most frequent first.
        """
        from ..exec.plan import plan_query

        plans = [
            plan_query(self.sets, q, hashbin_ratio=float("inf"), device=True,
                       mesh_shards=self.n_shards,
                       mesh_replicas=self.n_replicas,
                       shard_min_g=self.shard_min_g)
            for q in sample_queries
        ]
        return warm_from_plans(
            plans, lambda t: self.sets[t], top_k=top_k, b_tiers=b_tiers,
            use_pallas=self.use_pallas, mesh=self.mesh, axis=self.shard_axis,
            get_sharded_set=self.get_mesh_set,
            topology=self.topology, get_replica_set=self.get_replica_set,
        )
