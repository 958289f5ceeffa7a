"""Pure-jnp oracles for every Pallas kernel (the correctness references)."""
from __future__ import annotations

import jax
import jax.numpy as jnp


def paged_attention_ref(q, k_pages, v_pages, page_table, seq_lens):
    """Reference for kernels.paged_attention: dense gather + masked softmax."""
    B, H, D = q.shape
    N, page_size, KVH, _ = k_pages.shape
    P = page_table.shape[1]
    G = H // KVH
    scale = 1.0 / (D ** 0.5)

    pt = jnp.maximum(page_table, 0)
    k = k_pages[pt]                       # [B, P, page, KVH, D]
    v = v_pages[pt]
    S = P * page_size
    k = k.reshape(B, S, KVH, D).astype(jnp.float32)
    v = v.reshape(B, S, KVH, D).astype(jnp.float32)
    qh = q.reshape(B, KVH, G, D).astype(jnp.float32)

    s = jnp.einsum("bkgd,bskd->bkgs", qh, k) * scale
    pos = jnp.arange(S)[None, None, None, :]
    mask = pos < seq_lens[:, None, None, None]
    s = jnp.where(mask, s, -1e30)
    p = jax.nn.softmax(s, axis=-1)
    p = jnp.where(mask, p, 0.0)
    o = jnp.einsum("bkgs,bskd->bkgd", p, v)
    return o.reshape(B, H, D).astype(q.dtype)
