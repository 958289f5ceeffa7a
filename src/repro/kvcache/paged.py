"""Paged KV cache backed by PIM-malloc — the paper's technique as a
first-class serving feature.

Layout (distributed path): **per-sequence page pools**
    k_pages [L, B, P, page, KVH, hd]
Each sequence owns a reserved extent of P physical pages (exactly what the
buddy backend hands out at prefill); the page table indirects logical ->
physical *within* that extent, and single-page decode growth is served by
the thread-cache frontend. Sharding: B over ('pod','data') — every device
owns the pools AND page tables AND allocator metadata of its own sequences,
i.e. the paper's winning PIM-Metadata/PIM-Executed placement, with zero
cross-device metadata. KV heads / head_dim shard over 'model'.

The single-device serving path flattens the per-seq pools into the shared
pool the Pallas paged-attention kernel expects ([B*P, page, KVH, hd] with
global page ids), so the TPU kernel and the allocator-shared-pool story are
exercised end-to-end in examples/serve_paged.py.

`attend` implementations (explicit `impl=` argument; models thread
`ArchConfig.attend_impl` through — there is no module-global switch):
  * 'ref'    — pure-jnp batched gather + masked softmax; GSPMD-partitionable
               (used in pjit'd serve steps / the dry run).
  * 'kernel' — Pallas TPU kernel (scalar-prefetched page indices, online
               softmax in VMEM scratch).
"""
from __future__ import annotations

import math

import jax
import jax.numpy as jnp
import numpy as np

from repro.core import api
from repro.core.heap import AllocResponse

PAGE_UNIT = 16  # allocator bytes per page (smallest size class)


def pages_per_seq(max_seq: int, page_size: int) -> int:
    return math.ceil(max_seq / page_size)


def cache_spec(*, n_layers: int, batch: int, max_seq: int, page_size: int,
               kv_heads: int, head_dim: int, dtype):
    """ShapeDtypeStruct pytree for the paged cache (dry-run friendly)."""
    P = pages_per_seq(max_seq, page_size)
    sds = jax.ShapeDtypeStruct
    return {
        "k_pages": sds((n_layers, batch, P, page_size, kv_heads, head_dim), dtype),
        "v_pages": sds((n_layers, batch, P, page_size, kv_heads, head_dim), dtype),
        "page_table": sds((batch, P), jnp.int32),
        "seq_lens": sds((batch,), jnp.int32),
    }


def init_cache(*, n_layers: int, batch: int, max_seq: int, page_size: int,
               kv_heads: int, head_dim: int, dtype):
    """Zero cache with the identity page table (contiguous buddy extent)."""
    spec = cache_spec(n_layers=n_layers, batch=batch, max_seq=max_seq,
                      page_size=page_size, kv_heads=kv_heads,
                      head_dim=head_dim, dtype=dtype)
    P = spec["page_table"].shape[1]
    return {
        "k_pages": jnp.zeros(spec["k_pages"].shape, dtype),
        "v_pages": jnp.zeros(spec["v_pages"].shape, dtype),
        "page_table": jnp.broadcast_to(jnp.arange(P, dtype=jnp.int32),
                                       (batch, P)).copy(),
        "seq_lens": jnp.zeros((batch,), jnp.int32),
    }


def write_prefill(pages, kv, page_table):
    """pages [B,P,page,KVH,hd]; kv [B,S,KVH,hd]; S % page_size == 0.

    put_along_axis (NOT .at[bidx, idx]) so the scatter carries batching
    dims — GSPMD keeps the batch axis sharded instead of involuntarily
    replicating the pool across the data axis."""
    B, P, page_size, KVH, hd = pages.shape
    S = kv.shape[1]
    assert S % page_size == 0, (S, page_size)
    sp = S // page_size
    kv4 = kv.reshape(B, sp, page_size, KVH, hd).astype(pages.dtype)
    idx = jnp.clip(page_table[:, :sp], 0, P - 1)
    return jax.vmap(lambda p, i, v: p.at[i].set(v))(pages, idx, kv4)


def write_token(pages, kv, page_table, pos):
    """pages [B,P,page,KVH,hd]; kv [B,KVH,hd]; pos int32[B] (0-based slot).

    Flattens (P, page) so the write is one batched put_along_axis."""
    B, P, page_size, KVH, hd = pages.shape
    pidx = jnp.take_along_axis(page_table, (pos // page_size)[:, None], axis=1)[:, 0]
    pidx = jnp.clip(pidx, 0, P - 1)
    slot = pos % page_size
    return jax.vmap(lambda p, i, s, v: p.at[i, s].set(v))(
        pages, pidx, slot, kv.astype(pages.dtype))


def _attend_ref(q, k_pages, v_pages, page_table, seq_lens):
    """Batched-gather reference: per-seq pools stay local on the data axis.

    take_along_axis (batching dims!) + bf16 gathers; fp32 only inside the
    einsum accumulators."""
    B, H, D = q.shape
    _, P, page_size, KVH, _ = k_pages.shape
    G = H // KVH
    scale = 1.0 / (D ** 0.5)
    pt = jnp.clip(page_table, 0, P - 1)
    k = jax.vmap(lambda p, i: p[i])(k_pages, pt).reshape(B, P * page_size,
                                                         KVH, D)
    v = jax.vmap(lambda p, i: p[i])(v_pages, pt).reshape(B, P * page_size,
                                                         KVH, D)
    qh = q.reshape(B, KVH, G, D)
    s = jnp.einsum("bkgd,bskd->bkgs", qh.astype(k.dtype), k,
                   preferred_element_type=jnp.float32) * scale
    pos = jnp.arange(P * page_size)[None, None, None, :]
    mask = pos < seq_lens[:, None, None, None]
    s = jnp.where(mask, s, -1e30)
    p = jax.nn.softmax(s, axis=-1)
    p = jnp.where(mask, p, 0.0)
    o = jnp.einsum("bkgs,bskd->bkgd", p.astype(k.dtype), v,
                   preferred_element_type=jnp.float32)
    return o.reshape(B, H, D).astype(q.dtype)


def attend(q, k_pages, v_pages, page_table, seq_lens, impl: str = "ref"):
    """Decode attention over per-seq paged KV. q [B,H,hd] -> [B,H,hd]."""
    if impl == "kernel":
        from repro.kernels import ops
        B, P, page_size, KVH, hd = k_pages.shape
        kp = k_pages.reshape(B * P, page_size, KVH, hd)
        vp = v_pages.reshape(B * P, page_size, KVH, hd)
        pt_global = (jnp.arange(B, dtype=jnp.int32)[:, None] * P
                     + jnp.clip(page_table, 0, P - 1))
        return ops.paged_attention_op(q, kp, vp, pt_global, seq_lens)
    return _attend_ref(q, k_pages, v_pages, page_table, seq_lens)


def _ambient_mesh():
    """The active mesh when it has a 'model' axis; None otherwise."""
    from repro.parallel.meshctx import ambient_mesh
    mesh = ambient_mesh()
    if mesh is not None and "model" in mesh.axis_names:
        return mesh
    return None


def write_attend_seqpar(q, k_new, v_new, k_pages, v_pages, page_table, pos):
    """Flash-decoding under shard_map: pools shard their PHYSICAL page dim
    over 'model' (sequence parallelism). Each shard writes the new token iff
    it owns the target page (no cross-shard scatter), attends over its local
    pages with an online-softmax partial, and the partials combine with
    pmax/psum of [B, KVH, G(, hd)] stats — O(KB) collectives per layer
    instead of the GSPMD fallback's full-pool gathers/reduces.

    q [B,H,hd]; k_new/v_new [B,KVH,hd]; pools [B,P,page,KVH,hd]; pos [B].
    Returns (o [B,H,hd], k_pages, v_pages). Falls back to the write_token +
    attend pair when no 'model' mesh is ambient (single-device tests).
    """
    mesh = _ambient_mesh()
    if mesh is None:
        kp = write_token(k_pages, k_new, page_table, pos)
        vp = write_token(v_pages, v_new, page_table, pos)
        return attend(q, kp, vp, page_table, pos + 1), kp, vp

    from jax.sharding import PartitionSpec as P

    B, H, hd = q.shape
    _, Pn, page_size, KVH, _ = k_pages.shape
    G = H // KVH
    dp = tuple(a for a in mesh.axis_names if a != "model")
    dpb = dp if B % max(
        1, int(np.prod([mesh.shape[a] for a in dp]))) == 0 else None

    def local_fn(q, kn, vn, kp, vp, pt, pos):
        from jax import lax
        Bl = q.shape[0]
        Pl = kp.shape[1]
        midx = lax.axis_index("model")
        base = midx * Pl
        # ---- local write of the new token --------------------------------
        pidx = jnp.take_along_axis(pt, (pos // page_size)[:, None], axis=1)[:, 0]
        mine = (pidx >= base) & (pidx < base + Pl)
        li = jnp.clip(pidx - base, 0, Pl - 1)
        slot = pos % page_size

        def wr(p, i, s, v, w):
            return p.at[i, s].set(jnp.where(w, v.astype(p.dtype), p[i, s]))

        kp = jax.vmap(wr)(kp, li, slot, kn, mine)
        vp = jax.vmap(wr)(vp, li, slot, vn, mine)
        # ---- logical positions of local physical pages -------------------
        inv = jax.vmap(lambda row: jnp.full((Pn,), -1, jnp.int32).at[
            jnp.clip(row, 0, Pn - 1)].set(
                jnp.arange(Pn, dtype=jnp.int32)))(pt)
        inv_local = lax.dynamic_slice(inv, (jnp.int32(0), base), (Bl, Pl))
        grid = (inv_local[:, :, None] * page_size
                + jnp.arange(page_size)[None, None, :])
        valid = (inv_local[:, :, None] >= 0) & (grid <= pos[:, None, None])
        valid = valid.reshape(Bl, 1, 1, Pl * page_size)
        # ---- local flash partial ------------------------------------------
        k2 = kp.reshape(Bl, Pl * page_size, KVH, hd)
        v2 = vp.reshape(Bl, Pl * page_size, KVH, hd)
        qh = q.reshape(Bl, KVH, G, hd)
        s = jnp.einsum("bkgd,btkd->bkgt", qh.astype(k2.dtype), k2,
                       preferred_element_type=jnp.float32) / (hd ** 0.5)
        s = jnp.where(valid, s, -1e30)
        m = jnp.max(s, axis=-1)
        m_g = lax.pmax(m, "model")
        p = jnp.exp(s - m_g[..., None])
        p = jnp.where(valid, p, 0.0)
        l = lax.psum(jnp.sum(p, axis=-1), "model")
        o_p = jnp.einsum("bkgt,btkd->bkgd", p.astype(k2.dtype), v2,
                         preferred_element_type=jnp.float32)
        o = lax.psum(o_p, "model") / jnp.maximum(l, 1e-30)[..., None]
        return o.reshape(Bl, H, hd).astype(q.dtype), kp, vp

    pool_spec = P(dpb, "model", None, None, None)
    o, kp, vp = jax.shard_map(
        local_fn, mesh=mesh,
        in_specs=(P(dpb, None, None), P(dpb, None, None), P(dpb, None, None),
                  pool_spec, pool_spec, P(dpb, None), P(dpb,)),
        out_specs=(P(dpb, None, None), pool_spec, pool_spec),
        check_vma=False,
    )(q, k_new, v_new, k_pages, v_pages, page_table, pos)
    return o, kp, vp


class PagePool:
    """Host-side page allocator for serving: PIM-malloc manages page ids.

    Pages are allocator 'bytes' at PAGE_UNIT per page; ptr -> page_id =
    ptr // PAGE_UNIT. Built on a `repro.core.api.HeapClient`, so serving
    shares one allocator surface (and one jitted step) with the simulators
    and the serving engines, and every call also yields the DPU cost
    model's per-thread latencies (`pool.client.last_info`). One pool per
    device shard — a multi-device pool is `heap.MultiCoreHeap` / shard_map
    over the data axis (see examples/serve_paged.py).

    Every page free routes through the protocol's free path — a stale or
    repeated page id reaches the backend and shows up in
    `Stats.dropped_frees` (and as a deterministic ``double_free`` /
    ``use_after_free`` tag on the ``sanitizer`` kind) instead of being
    silently absorbed host-side (pinned in tests/test_serve_decode.py).
    """

    def __init__(self, n_pages: int, num_threads: int = 16, kind: str = "sw",
                 client: api.HeapClient = None, alloc=None):
        """``client`` injects a `HeapClient` whose heap spans
        n_pages * PAGE_UNIT bytes — e.g. a
        `repro.workloads.trace.RecordingAllocator`, so serving churn can be
        captured as an AllocRequest tape and replayed on every backend.

        ``alloc`` is the deprecated PR-4 injection hook: an
        Allocator-compatible handle (or zero-arg factory returning one).
        Still accepted, but warns and is adapted via `HeapClient.wrap`.
        """
        assert n_pages & (n_pages - 1) == 0, "n_pages must be pow2"
        self.n_pages = n_pages
        if alloc is not None:
            import warnings
            warnings.warn(
                "PagePool(alloc=...) is deprecated: pass client=HeapClient "
                "(or any HeapClient subclass); bare handles/factories are "
                "adapted via HeapClient.wrap for now",
                DeprecationWarning, stacklevel=2)
            if client is not None:
                raise TypeError("pass either client= or (deprecated) alloc=")
            client = api.HeapClient.wrap(alloc)
        if client is None:
            client = api.HeapClient(
                heap_bytes=n_pages * PAGE_UNIT, num_threads=num_threads,
                kind=kind,
            )
        elif not isinstance(client, api.HeapClient):
            raise TypeError(
                f"client must be a HeapClient, got {type(client).__name__!r}"
                " (legacy handles go through the deprecated alloc= hook)")
        assert client.cfg.heap_bytes == n_pages * PAGE_UNIT, \
            (client.cfg.heap_bytes, n_pages * PAGE_UNIT)
        self.client = client
        # back-compat alias: pre-PR-8 callers read `pool.alloc.last_info`
        self.alloc = client
        self.cfg = self.client.cfg.pm  # block_bytes=4096: 256-page refills

    def alloc_pages(self, n: int, thread: int = 0) -> jnp.ndarray:
        """Contiguous extent of `n` pages; returns page ids [n] (empty on OOM)."""
        ptr = self.client.malloc(n * PAGE_UNIT, thread=thread)
        if ptr < 0:
            return jnp.zeros((0,), jnp.int32)
        return ptr // PAGE_UNIT + jnp.arange(n, dtype=jnp.int32)

    def alloc_page_batch(self, threads) -> tuple[jnp.ndarray, AllocResponse]:
        """One single-page allocation per requesting thread (decode growth).
        threads: bool[T] mask. Returns (int32[T] page ids (-1 = none), resp)."""
        threads = jnp.asarray(threads)
        sizes = jnp.where(threads, PAGE_UNIT, 0).astype(jnp.int32)
        resp = self.client.malloc_batch(sizes, threads)
        return jnp.where(resp.ptr >= 0, resp.ptr // PAGE_UNIT, -1), resp

    def grow_extent(self, first_page: int, n_pages: int,
                    thread: int = 0) -> tuple[jnp.ndarray, bool]:
        """realloc an extent to `n_pages` pages.

        Returns (page ids [n], moved). ids is empty on OOM (the old extent
        then remains live). When `moved` is True the allocator relocated the
        extent and freed the old pages: the caller MUST copy the old pages'
        KV contents into the returned ids before its next allocation, or the
        old pages may be handed to another sequence.
        """
        new_ptr = self.client.realloc(int(first_page) * PAGE_UNIT,
                                      n_pages * PAGE_UNIT, thread=thread)
        if new_ptr < 0:
            return jnp.zeros((0,), jnp.int32), False
        moved = bool(self.client.last_info.moved[thread])
        return new_ptr // PAGE_UNIT + jnp.arange(n_pages, dtype=jnp.int32), moved

    def free_page_batch(self, pages) -> AllocResponse:
        """Free one page per thread slot (decode-page reclaim): pages
        int32[T] page ids, -1 = nothing to free on that slot."""
        pages = jnp.asarray(pages, jnp.int32)
        ptrs = jnp.where(pages >= 0, pages * PAGE_UNIT, -1)
        return self.client.free_batch(ptrs)

    def free_extent(self, first_page: int, thread: int = 0) -> None:
        self.client.free(int(first_page) * PAGE_UNIT, thread=thread)

    def evict(self, first_page: int, decode_pages, thread: int = 0) -> dict:
        """Session-end eviction: free ALL decode pages, then the extent,
        every free through the protocol.

        ``decode_pages`` (any length — chunked into T-wide free rounds; the
        pre-PR-8 recorder truncated at T and silently leaked the tail) and
        the extent at ``first_page`` (skipped when < 0, e.g. a session that
        died before its prefill extent was allocated). Returns
        ``{"freed_pages", "dropped_frees"}`` — a nonzero ``dropped_frees``
        means a stale/double page id reached the backend's dropped-free
        path (deterministically tagged on the ``sanitizer`` kind).
        """
        T = self.client.cfg.num_threads
        ids = [int(p) for p in np.asarray(decode_pages, np.int64).reshape(-1)
               if int(p) >= 0]
        freed = dropped = 0
        for i in range(0, len(ids), T):
            chunk = np.full((T,), -1, np.int32)
            chunk[:len(ids[i:i + T])] = ids[i:i + T]
            resp = self.free_page_batch(chunk)
            freed += len(ids[i:i + T])
            dropped += int(np.asarray((resp.path == 2)
                                      & (chunk >= 0)).sum())
        if int(first_page) >= 0:
            self.free_extent(first_page, thread=thread)
            info = self.client.last_info
            dropped += int(np.asarray(info.path[thread] == 2))
        return {"freed_pages": freed, "dropped_frees": dropped}

    def gc(self) -> None:
        self.client.gc()

    @property
    def stats(self) -> dict:
        return self.client.stats
