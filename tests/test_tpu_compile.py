"""Compile the Pallas kernels and whole bucket programs for a TPU v5e.

No chip is needed: the TPU compiler builds for a described ``v5e:2x2``
topology, so what Mosaic or XLA would refuse on the chip (a bool
reduction, too much fast memory, a program over the device's 16 GB)
fails here.  Widths are the ones ``chip_smoke.py`` serves: up to 4
terms, 2^12 groups, group tiers up to 64, batch tiers up to 4, suggest
buckets of 64 candidates at depth 10, and the 2x2 topology's z-sharded
row programs.

The topology is described inside a fixture, never at import: only one
process at a time may load the TPU library.
"""
import os
import re

import jax
import jax.numpy as jnp
import pytest
import numpy as np
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P
from jax.sharding import SingleDeviceSharding

from repro.core import engine
from repro.kernels import ops
from repro.kernels.bitmap_filter import bitmap_filter_pallas
from repro.kernels.count import pair_count_pallas
from repro.kernels.group_intersect import group_match_pallas

DEVICE_BYTES = 16 * 10**9   # one v5e chip's HBM


@pytest.fixture(scope="module")
def v5e():
    """The four chips of a described v5e:2x2, with the persistent compile
    cache off (what it would write cannot be read back here)."""
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache

    with pytest.MonkeyPatch.context() as mp:
        if "TPU_LOG_DIR" not in os.environ:
            mp.setenv("TPU_LOG_DIR", "disabled")
        try:
            topo = topologies.get_topology_desc(platform="tpu",
                                                topology_name="v5e:2x2")
        except Exception as e:
            pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
        was = jax.config.jax_enable_compilation_cache
        jax.config.update("jax_enable_compilation_cache", False)
        compilation_cache.reset_cache()
        yield topo.devices
        jax.config.update("jax_enable_compilation_cache", was)
        compilation_cache.reset_cache()


@pytest.fixture(scope="module")
def chip(v5e):
    """A sharding on one chip of the described v5e."""
    return SingleDeviceSharding(v5e[0])


def _spec(sharding, shape, dtype=jnp.int32):
    return jax.ShapeDtypeStruct(shape, dtype, sharding=sharding)


def _check_compiled(lowered, kernel=True):
    compiled = lowered.compile()
    text = compiled.as_text()
    assert ("tpu_custom_call" in text) == kernel
    mem = compiled.memory_analysis()
    assert mem.argument_size_in_bytes + mem.temp_size_in_bytes < DEVICE_BYTES
    return text


@pytest.mark.parametrize("kernel,widths", [
    pytest.param("bitmap_filter", None, id="bitmap_filter"),
    pytest.param("group_match", (4, 1 << 12, 64, 64), id="group_match"),
    # the largest re-run the gov2-conj cell warms: B = 256 at capacity 2^11
    pytest.param("group_match", (256, 1 << 11, 32, 32),
                 id="group_match-rerun-32x32"),
    pytest.param("group_match", (256, 1 << 11, 32, 64),
                 id="group_match-rerun-32x64"),
    pytest.param("pair_count", None, id="pair_count"),
])
def test_kernel_compiles_for_v5e(chip, kernel, widths):
    if kernel == "bitmap_filter":
        fn = lambda x: bitmap_filter_pallas(x, interpret=False)
        args = (_spec(chip, (4, 4, 1 << 12, 2, 8), jnp.uint32),)
    elif kernel == "group_match":
        fn = lambda a, b: group_match_pallas(a, b, interpret=False)
        bsz, s, ga, gb = widths
        args = (_spec(chip, (bsz, s, ga)), _spec(chip, (bsz, s, gb)))
    else:
        fn = lambda a, b: pair_count_pallas(a, b, interpret=False)
        args = (_spec(chip, (1, 64, 1 << 10, 64)),) * 2
    text = _check_compiled(jax.jit(fn).lower(*args))
    # the kernel keeps its own name in the compiled program (and so in the
    # device trace the benchmark's readers match), whatever its wrapper
    assert re.search(rf"%{kernel}(\.\d+)? = [^\n]*custom-call", text)


@pytest.mark.parametrize("capacity", [engine.default_capacity((12,)), 1 << 12])
def test_point_bucket_compiles_for_v5e(chip, monkeypatch, capacity):
    """A whole 4-term conjunctive bucket at B=4: the first pass at the
    default survivor capacity, and the overflow re-run at capacity G."""
    monkeypatch.setattr(ops, "_on_tpu", lambda: True)
    ts, gmaxes, b = (11, 12, 12, 12), (32, 32, 64, 64), 4
    vals = tuple(tuple(_spec(chip, (1 << t, g)) for _ in range(b))
                 for t, g in zip(ts, gmaxes))
    images = tuple(tuple(_spec(chip, (1 << t, 2, 8), jnp.uint32)
                         for _ in range(b)) for t in ts)
    _check_compiled(engine._intersect_k_batch.lower(
        vals, images, ts, gmaxes, capacity, "auto"))


def test_count_bucket_compiles_for_v5e(chip, monkeypatch):
    """A suggest bucket: one probe against 64 candidates at depth 10."""
    monkeypatch.setattr(ops, "_on_tpu", lambda: True)
    ts, gmaxes, c = (10, 10), (64, 64), 64
    probe = (_spec(chip, (1 << ts[0], gmaxes[0])),)
    cands = (tuple(_spec(chip, (1 << ts[1], gmaxes[1])) for _ in range(c)),)
    _check_compiled(engine._intersect_count_batch.lower(
        probe, cands, _spec(chip, (1,)), ts, gmaxes, 8, "auto"))


def test_expr_bucket_compiles_for_v5e(chip):
    """A ``(a|b)&c`` bucket at B=4: leaves, densify and sort-merge passes
    (no Pallas kernel on this path)."""
    eshape = ("&", ("|", "T", "T"), "T")
    ts, gmaxes, b = (12, 12, 12), (32, 32, 32), 4
    vals = tuple(tuple(_spec(chip, (1 << t, g)) for _ in range(b))
                 for t, g in zip(ts, gmaxes))
    _check_compiled(engine._eval_expr_batch.lower(
        vals, eshape, ts, gmaxes, engine.default_expr_capacity(ts, gmaxes)),
        kernel=False)


def test_row_sharded_bucket_compiles_for_v5e(v5e, monkeypatch):
    """The 2x2 topology's second replica row: a 3-term bucket z-sharded
    over chips 2 and 3."""
    monkeypatch.setattr(ops, "_on_tpu", lambda: True)
    mesh = Mesh(np.asarray(v5e[2:4]), ("shard",))
    ts, gmaxes, b = (11, 12, 12), (32, 32, 64), 2
    vals = tuple(tuple(_spec(NamedSharding(mesh, P("shard", None)),
                             (1 << t, g)) for _ in range(b))
                 for t, g in zip(ts, gmaxes))
    images = tuple(tuple(_spec(NamedSharding(mesh, P("shard", None, None)),
                               (1 << t, 2, 8), jnp.uint32) for _ in range(b))
                   for t in ts)
    _check_compiled(engine._intersect_k_sharded_batch.lower(
        vals, images, mesh, "shard", ts, gmaxes,
        engine.default_capacity_per_shard(ts, 2), "auto",
        trace_counter="mesh2d_traces"))
