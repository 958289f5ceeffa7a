"""Per-kernel validation: Pallas (interpret mode) vs pure-jnp ref oracles,
with shape/dtype sweeps."""
import jax.numpy as jnp
import numpy as np
import pytest

from repro.kernels import ops


# ------------------------------------------------------------ paged attention
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
@pytest.mark.parametrize("B,H,KVH,D,pages,page_size", [
    (2, 4, 2, 128, 4, 128),
    (1, 8, 1, 128, 2, 128),   # MQA
    (3, 6, 6, 128, 3, 128),   # MHA
])
def test_paged_attention_matches_ref(B, H, KVH, D, pages, page_size, dtype):
    rng = np.random.RandomState(2)
    N = pages * B + 2
    q = jnp.asarray(rng.randn(B, H, D), dtype) * 0.1
    k_pages = jnp.asarray(rng.randn(N, page_size, KVH, D), dtype) * 0.1
    v_pages = jnp.asarray(rng.randn(N, page_size, KVH, D), dtype) * 0.1
    # each sequence gets distinct pages (as the allocator would hand out)
    pt = jnp.asarray(
        rng.permutation(N)[: B * pages].reshape(B, pages), jnp.int32)
    seq_lens = jnp.asarray(rng.randint(1, pages * page_size, size=(B,)), jnp.int32)
    out_k = ops.paged_attention_op(q, k_pages, v_pages, pt, seq_lens,
                                   interpret=True)
    out_r = ops.paged_attention_ref(q, k_pages, v_pages, pt, seq_lens)
    atol = 2e-2 if dtype == jnp.bfloat16 else 2e-5
    np.testing.assert_allclose(np.asarray(out_k, np.float32),
                               np.asarray(out_r, np.float32), atol=atol, rtol=atol)


def test_paged_attention_respects_page_table():
    """Swapping page table rows permutes outputs accordingly."""
    B, H, KVH, D, pages, page_size = 2, 2, 2, 128, 2, 128
    rng = np.random.RandomState(3)
    q = jnp.asarray(rng.randn(1, H, D), jnp.float32)
    q2 = jnp.concatenate([q, q], axis=0)
    k_pages = jnp.asarray(rng.randn(6, page_size, KVH, D), jnp.float32)
    v_pages = jnp.asarray(rng.randn(6, page_size, KVH, D), jnp.float32)
    pt = jnp.array([[0, 1], [2, 3]], jnp.int32)
    sl = jnp.array([page_size * 2, page_size * 2], jnp.int32)
    out = ops.paged_attention_op(q2, k_pages, v_pages, pt, sl, interpret=True)
    out_sw = ops.paged_attention_op(q2, k_pages, v_pages, pt[::-1], sl,
                                    interpret=True)
    np.testing.assert_allclose(np.asarray(out[0]), np.asarray(out_sw[1]),
                               atol=1e-6)
    assert not np.allclose(np.asarray(out[0]), np.asarray(out[1]))


# ------------------------------------------------------------ flash attention
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
@pytest.mark.parametrize("B,S,T,H,KVH,hd,causal,window", [
    (2, 256, 256, 4, 2, 128, True, 0),
    (1, 512, 512, 4, 1, 128, True, 128),   # MQA + sliding window
    (2, 128, 384, 6, 6, 128, False, 0),    # MHA, cross-shaped (S != T)
])
def test_flash_kernel_matches_dense(B, S, T, H, KVH, hd, causal, window, dtype):
    from repro.models import layers

    rng = np.random.RandomState(7)
    q = jnp.asarray(rng.randn(B, S, H, hd), dtype) * 0.2
    k = jnp.asarray(rng.randn(B, T, KVH, hd), dtype) * 0.2
    v = jnp.asarray(rng.randn(B, T, KVH, hd), dtype) * 0.2
    out_k = ops.flash_attention_op(q, k, v, causal=causal, window=window,
                                   block_q=128, block_kv=128, interpret=True)
    out_r = layers.attention(q, k, v, causal=causal, window=window)
    atol = 2.5e-2 if dtype == jnp.bfloat16 else 3e-5
    np.testing.assert_allclose(np.asarray(out_k, np.float32),
                               np.asarray(out_r, np.float32),
                               atol=atol, rtol=atol)


def test_flash_kernel_odd_blocks():
    """Block sizes auto-fit non-multiple sequence lengths."""
    from repro.models import layers

    rng = np.random.RandomState(8)
    q = jnp.asarray(rng.randn(1, 192, 2, 64), jnp.float32) * 0.3
    k = jnp.asarray(rng.randn(1, 192, 2, 64), jnp.float32) * 0.3
    v = jnp.asarray(rng.randn(1, 192, 2, 64), jnp.float32) * 0.3
    out_k = ops.flash_attention_op(q, k, v, causal=True, block_q=128,
                                   block_kv=128, interpret=True)
    out_r = layers.attention(q, k, v, causal=True)
    np.testing.assert_allclose(np.asarray(out_k), np.asarray(out_r),
                               atol=3e-5, rtol=3e-5)
