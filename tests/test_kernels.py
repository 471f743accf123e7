"""Per-kernel shape/dtype sweeps: Pallas (interpret=True) vs ref.py oracle."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.kernels import ref
from repro.kernels.flash_attention import flash_attention
from repro.kernels.lru_scan import lru_scan
from repro.kernels.segment_sum import segment_sum


# ---------------------------------------------------------------- segment_sum
@pytest.mark.parametrize("m,F,n", [(16, 8, 4), (100, 16, 10), (512, 128, 64),
                                   (33, 7, 5), (1, 4, 3)])
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_segment_sum_sweep(m, F, n, dtype):
    key = jax.random.PRNGKey(m * 1000 + F)
    vals = jax.random.normal(key, (m, F), dtype)
    segs = jnp.sort(jax.random.randint(key, (m,), 0, n))
    got = segment_sum(vals, segs, n, edge_block=64, feat_block=32,
                      interpret=True)
    want = ref.segment_sum(vals.astype(jnp.float32), segs, n)
    tol = 1e-5 if dtype == jnp.float32 else 3e-2
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               atol=tol, rtol=tol)


def test_segment_sum_empty_segments():
    vals = jnp.ones((8, 4), jnp.float32)
    segs = jnp.array([0, 0, 0, 0, 5, 5, 5, 5])   # segments 1-4 empty
    got = segment_sum(vals, segs, 7, interpret=True)
    assert np.asarray(got)[1:5].sum() == 0
    assert np.asarray(got)[0].sum() == 16
    assert np.asarray(got)[6].sum() == 0


def test_segment_sum_keeps_every_f32_bit():
    # the MXU multiplies bf16; the kernel's three bf16 pieces must carry a
    # lone f32 value through bit for bit
    vals = jnp.array([[1.2345678e-5], [-3.3333333], [7e-30], [123456.79]],
                     jnp.float32)
    got = segment_sum(vals, jnp.arange(4), 4, interpret=True)
    np.testing.assert_array_equal(np.asarray(got), np.asarray(vals))


# ------------------------------------------------------------ flash_attention
@pytest.mark.parametrize("B,Hq,Hkv,S,hd", [
    (1, 2, 2, 128, 32), (2, 4, 2, 256, 64), (1, 8, 1, 128, 16),
])
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_flash_causal_sweep(B, Hq, Hkv, S, hd, dtype):
    keys = jax.random.split(jax.random.PRNGKey(S + Hq), 3)
    q = jax.random.normal(keys[0], (B, Hq, S, hd), dtype)
    k = jax.random.normal(keys[1], (B, Hkv, S, hd), dtype)
    v = jax.random.normal(keys[2], (B, Hkv, S, hd), dtype)
    got = flash_attention(q, k, v, causal=True, q_block=64, kv_block=64,
                          interpret=True)
    want = ref.flash_attention(q, k, v, causal=True)
    tol = 2e-4 if dtype == jnp.float32 else 3e-2
    np.testing.assert_allclose(np.asarray(got, np.float32),
                               np.asarray(want, np.float32),
                               atol=tol, rtol=tol)


@pytest.mark.parametrize("window", [32, 64, 128])
def test_flash_windowed(window):
    B, H, S, hd = 1, 2, 256, 32
    keys = jax.random.split(jax.random.PRNGKey(window), 3)
    q = jax.random.normal(keys[0], (B, H, S, hd), jnp.float32)
    k = jax.random.normal(keys[1], (B, H, S, hd), jnp.float32)
    v = jax.random.normal(keys[2], (B, H, S, hd), jnp.float32)
    got = flash_attention(q, k, v, causal=True, window=window,
                          q_block=32, kv_block=32, interpret=True)
    want = ref.flash_attention(q, k, v, causal=True, window=window)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               atol=2e-4, rtol=2e-4)


def test_flash_matches_model_attention_path():
    """Kernel agrees with the portable chunked path used by the models."""
    from repro.nn.attention import _causal_blocked, _gqa_shape
    from repro.configs import all_configs, reduced
    cfg = reduced(all_configs()["qwen2.5-14b"], kv_chunk=32)
    B, Hq, Hkv, S, hd = 1, 4, 2, 128, 16
    keys = jax.random.split(jax.random.PRNGKey(7), 3)
    q = jax.random.normal(keys[0], (B, Hq, S, hd), jnp.float32)
    k = jax.random.normal(keys[1], (B, Hkv, S, hd), jnp.float32)
    v = jax.random.normal(keys[2], (B, Hkv, S, hd), jnp.float32)
    kern = flash_attention(q, k, v, causal=True, q_block=32, kv_block=32,
                           interpret=True)
    port = _causal_blocked(_gqa_shape(q, Hkv), k, v, cfg)
    port = port.reshape(B, Hq, S, hd)
    np.testing.assert_allclose(np.asarray(kern), np.asarray(port),
                               atol=2e-4, rtol=2e-4)


# ------------------------------------------------------------------ lru_scan
@pytest.mark.parametrize("B,S,C", [(1, 64, 32), (2, 256, 64), (1, 100, 16),
                                   (3, 8, 8)])
def test_lru_scan_sweep(B, S, C):
    keys = jax.random.split(jax.random.PRNGKey(B * S + C), 2)
    a = jax.random.uniform(keys[0], (B, S, C), jnp.float32, 0.5, 0.999)
    b = jax.random.normal(keys[1], (B, S, C), jnp.float32)
    got = lru_scan(a, b, channel_block=16, time_chunk=32, interpret=True)
    want = ref.lru_scan(a, b)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               atol=1e-5, rtol=1e-4)


def test_lru_scan_time_tiling_carry():
    """Wrapper time-tiling (S > MAX_RESIDENT_S) chains carries correctly."""
    import repro.kernels.lru_scan as mod
    old = mod.MAX_RESIDENT_S
    mod.MAX_RESIDENT_S = 64
    try:
        B, S, C = 1, 200, 8
        keys = jax.random.split(jax.random.PRNGKey(0), 2)
        a = jax.random.uniform(keys[0], (B, S, C), jnp.float32, 0.5, 0.999)
        b = jax.random.normal(keys[1], (B, S, C), jnp.float32)
        got = lru_scan(a, b, channel_block=8, time_chunk=32, interpret=True)
        want = ref.lru_scan(a, b)
        np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                                   atol=1e-5, rtol=1e-4)
    finally:
        mod.MAX_RESIDENT_S = old


def test_lru_matches_rglru_block_path():
    """graph/nn integration: rglru_forward(use_kernel=True) == default path."""
    from repro.configs import all_configs, reduced
    from repro.nn.recurrent import init_rglru_block, rglru_forward
    cfg = reduced(all_configs()["recurrentgemma-2b"])
    p = init_rglru_block(jax.random.PRNGKey(1), cfg)
    x = jax.random.normal(jax.random.PRNGKey(2), (2, 32, cfg.d_model),
                          jnp.float32)
    y0 = rglru_forward(p, x, cfg, use_kernel=False)
    y1 = rglru_forward(p, x, cfg, use_kernel=True)
    np.testing.assert_allclose(np.asarray(y0), np.asarray(y1),
                               atol=1e-4, rtol=1e-4)


# ----------------------------------------------------------- snapshot_resolve
@pytest.mark.parametrize("N,K", [(16, 4), (100, 8), (1024, 3), (3, 1)])
def test_snapshot_resolve_matches_versioned_array(N, K):
    """The Pallas kernel implements the paper's snapshot rule exactly
    (oracle: repro.core.versioned.resolve_versions)."""
    from repro.core.versioned import resolve_versions
    from repro.kernels.snapshot_resolve import snapshot_resolve
    rng = np.random.default_rng(N + K)
    maxv = np.iinfo(np.int32).max
    vers = np.sort(rng.integers(0, 1000, (N, K)), axis=1).astype(np.int32)
    # pad a random suffix per row
    fill = rng.integers(0, K + 1, N)
    for i in range(N):
        vers[i, fill[i]:] = maxv
    vals = rng.standard_normal((N, K)).astype(np.float32)
    q = 500
    out, idx = snapshot_resolve(jnp.asarray(vers), jnp.asarray(vals), q,
                                item_block=32, interpret=True)
    oracle_idx = np.asarray(resolve_versions(vers, q))
    np.testing.assert_array_equal(np.asarray(idx), oracle_idx)
    for i in range(N):
        if oracle_idx[i] >= 0:
            assert np.asarray(out)[i] == vals[i, oracle_idx[i]]
        else:
            assert np.asarray(out)[i] == 0.0
