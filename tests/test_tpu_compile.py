"""Compile the served path's device programs for a TPU v5e chip that is
described, not attached: the Pallas kernels natively (no interpret mode)
and the frontier sweeps at the ``chip_smoke.py`` shapes (graph500-22:
2,396,657 vertices, 2^26 padded edges, 16-query windows). Each must
compile and fit one chip's 16 GiB of HBM.

The topology is described inside a fixture, never at import time, so every
test worker collects the same tests and only the one running this file
loads the TPU compiler.
"""
import os

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from repro.graph import compute as gc
from repro.kernels import segment_sum as ss
from repro.kernels import snapshot_resolve as sr

HBM_BYTES = 16 * 2**30
EDGE_ROWS = 1 << 26            # pad_pow2(64,155,735)
VERTICES = 2_396_657
WINDOW = 16


@pytest.fixture(scope="module")
def topo():
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from jax.experimental import topologies
    try:
        return topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:  # noqa: BLE001 — any failure means "cannot"
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")


@pytest.fixture(scope="module")
def one_chip(topo):
    # a compile for a described chip is written to the persistent cache
    # but cannot be read back without one; keep these compiles out of it
    from jax.experimental.compilation_cache import compilation_cache
    enabled = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield SingleDeviceSharding(topo.devices[0])
    jax.config.update("jax_enable_compilation_cache", enabled)
    compilation_cache.reset_cache()


def _compile(one_chip, fn, *shapes):
    args = [jax.ShapeDtypeStruct(s, d, sharding=one_chip)
            for s, d in shapes]
    compiled = jax.jit(fn).lower(*args).compile()
    mem = compiled.memory_analysis()
    used = (mem.argument_size_in_bytes + mem.output_size_in_bytes
            + mem.temp_size_in_bytes)
    assert used < HBM_BYTES, f"{used / 2**30:.2f} GiB does not fit one chip"
    return compiled


def test_liveness_mask_compiles_at_2_26_rows(one_chip):
    compiled = _compile(
        one_chip, lambda c, d, q: sr.liveness_mask(c, d, q),
        ((EDGE_ROWS,), jnp.int32), ((EDGE_ROWS,), jnp.int32),
        ((), jnp.int32))
    assert "tpu_custom_call" in compiled.as_text()


@pytest.mark.parametrize("features", [1, 128])
def test_segment_sum_compiles_at_its_bound(one_chip, features):
    # one feature column is the PageRank shape (edge factor 16)
    n = ss.MAX_SEGMENTS
    m = 16 * n if features == 1 else 4096
    compiled = _compile(
        one_chip, lambda v, s: ss.segment_sum(v, s, n),
        ((m, features), jnp.float32), ((m,), jnp.int32))
    assert "tpu_custom_call" in compiled.as_text()


def test_segment_sum_is_refused_one_row_block_past_its_bound(one_chip):
    n = ss.MAX_SEGMENTS + 1
    with pytest.raises(Exception, match="vmem"):
        _compile(one_chip, lambda v, s: ss.segment_sum(v, s, n),
                 ((16 * n, 1), jnp.float32), ((16 * n,), jnp.int32))


@pytest.mark.parametrize("sweep", ["khop", "reach"])
def test_frontier_sweep_fits_one_chip(one_chip, sweep):
    edges = ((EDGE_ROWS,), jnp.int32)
    reach0 = ((VERTICES, WINDOW), jnp.bool_)
    if sweep == "khop":
        _compile(one_chip,
                 lambda s, d, r: gc._batched_khop(s, d, r, VERTICES, 2),
                 edges, edges, reach0)
    else:
        _compile(one_chip,
                 lambda s, d, r, t, h: gc._batched_reach(s, d, r, t, h,
                                                         VERTICES),
                 edges, edges, reach0, ((WINDOW,), jnp.int32),
                 ((), jnp.int32))
