"""Metadata-cache simulators for the buddy allocator's tree traversals.

Two designs from the paper, both consuming the same node-index traces the
allocator emits (`BuddyEvent.trace` / `MallocEvent.trace`):

* `SWBuffer`  — PIM-malloc-SW's *software-managed metadata buffer* (Fig 12a):
  a single contiguous window of metadata words staged in scratchpad. A miss
  flushes the whole buffer and refills it around the requested word
  (coarse-grained), charging one DMA setup + `buf_bytes` of DRAM traffic.

* `BuddyCache` — PIM-malloc-HW/SW's hardware *buddy cache* (Fig 11-13):
  an `n_entries`-way fully-associative CAM of 4-byte metadata words with true
  LRU replacement. A miss fetches ONLY the requested word (fine-grained):
  one DMA setup + `word_bytes` of traffic, evicting the LRU entry
  (`lookup_bc` / `read_bc` / `write_bc` semantics).

Metadata addressing follows the paper's 2-bit-per-node packing: 16 tree
nodes per 4-byte word, so `word = node // 16` and a 16-entry cache holds
64 B = 256 nodes — exactly Fig 15's saturation arithmetic.
"""
from __future__ import annotations

import dataclasses
from typing import NamedTuple

import jax.numpy as jnp
from jax import lax

NODES_PER_WORD = 16  # 2 bits/node, 4-byte words
WORD_BYTES = 4


@dataclasses.dataclass(frozen=True)
class SWBufferConfig:
    """Software-managed metadata buffer: a *direct-mapped* line cache.

    'Caching recently accessed metadata and its neighboring entries' (paper
    Sec 3.2): a miss flushes the mapped line and DMAs a contiguous
    `line_bytes` block around the requested word. Coarse-grained management
    (whole-line flush+refill, trivial index mapping) is what a wimpy DPU can
    afford in software; the paper's attempted SW LRU was a net loss
    (Sec 4.2), so no LRU here — that is the HW buddy cache's edge.

    Default: 512 B of the 64 KB WRAM (shared by up to 24 tasklets' stacks and
    application working set), 64 B lines -> 8 lines. Direct mapping makes the
    buddy's top-of-tree words conflict with deep-level words, reproducing the
    thrash the HW cache's associativity + LRU eliminates.
    """

    buf_bytes: int = 512
    line_bytes: int = 64

    @property
    def n_lines(self) -> int:
        return self.buf_bytes // self.line_bytes

    @property
    def line_words(self) -> int:
        return self.line_bytes // WORD_BYTES


class SWBufferState(NamedTuple):
    tags: jnp.ndarray  # int32[n_lines] resident line address, -1 = empty


def sw_buffer_init(cfg: SWBufferConfig) -> SWBufferState:
    return SWBufferState(tags=jnp.full((cfg.n_lines,), -1, jnp.int32))


def sw_buffer_access(cfg: SWBufferConfig, st: SWBufferState, node):
    """One metadata access. Returns (state, hit bool, dram_bytes int32)."""
    valid = node >= 0
    word = jnp.maximum(node, 0) // NODES_PER_WORD
    line = word // cfg.line_words
    # one-hot select over the lines, not an indexed read and write: under
    # the fleet's vmaps a per-core index is a gather and a scatter per access
    sel = lax.iota(jnp.int32, cfg.n_lines) == line % cfg.n_lines
    hit = valid & jnp.any(sel & (st.tags == line))
    miss = valid & ~hit
    tags = jnp.where(sel & miss, line, st.tags)
    dram = jnp.where(miss, cfg.line_bytes, 0).astype(jnp.int32)
    return SWBufferState(tags=tags), hit, dram


@dataclasses.dataclass(frozen=True)
class BuddyCacheConfig:
    n_entries: int = 16  # 16 x 4 B = 64 B (paper's design point)


class BuddyCacheState(NamedTuple):
    tags: jnp.ndarray       # int32[E] word addresses, -1 invalid
    last_used: jnp.ndarray  # int32[E] LRU timestamps (-1 invalid => first victim)
    clock: jnp.ndarray      # int32 global access counter


def buddy_cache_init(cfg: BuddyCacheConfig) -> BuddyCacheState:
    return BuddyCacheState(
        tags=jnp.full((cfg.n_entries,), -1, jnp.int32),
        last_used=jnp.full((cfg.n_entries,), -1, jnp.int32),
        clock=jnp.int32(0),
    )


def buddy_cache_access(cfg: BuddyCacheConfig, st: BuddyCacheState, node):
    """lookup_bc + (read_bc | evict + write_bc). Returns (state, hit, dram_bytes)."""
    del cfg
    valid = node >= 0
    word = jnp.maximum(node, 0) // NODES_PER_WORD
    match = st.tags == word
    hit = valid & jnp.any(match)
    hit_idx = jnp.argmax(match)
    victim = jnp.argmin(st.last_used)  # invalid entries (-1) chosen first
    idx = jnp.where(hit, hit_idx, victim)
    do = valid
    # one-hot select over the entries (see sw_buffer_access)
    sel = (lax.iota(jnp.int32, st.tags.shape[0]) == idx) & do
    tags = jnp.where(sel, word, st.tags)
    last = jnp.where(sel, st.clock, st.last_used)
    clock = st.clock + do.astype(jnp.int32)
    dram = jnp.where(valid & ~hit, WORD_BYTES, 0).astype(jnp.int32)
    return BuddyCacheState(tags=tags, last_used=last, clock=clock), hit, dram


class TraceStats(NamedTuple):
    hits: jnp.ndarray        # int32[...]: per-op metadata hits
    misses: jnp.ndarray      # int32[...]
    dram_bytes: jnp.ndarray  # int32[...]


def simulate_traces(access_fn, cache_state, traces):
    """Run a cache sim over [B, L] node traces (ops in serialization order).

    access_fn: (state, node) -> (state, hit, dram_bytes)
    Returns (final_state, TraceStats with [B] per-op aggregates).
    """

    def per_op(cache_state, trace):
        def per_access(carry, node):
            cs, h, m, d = carry
            cs, hit, dram = access_fn(cs, node)
            valid = node >= 0
            h = h + (valid & hit).astype(jnp.int32)
            m = m + (valid & ~hit).astype(jnp.int32)
            d = d + dram
            return (cs, h, m, d), None

        (cache_state, h, m, d), _ = lax.scan(
            per_access, (cache_state, jnp.int32(0), jnp.int32(0), jnp.int32(0)), trace
        )
        return cache_state, (h, m, d)

    cache_state, (h, m, d) = lax.scan(per_op, cache_state, traces)
    return cache_state, TraceStats(hits=h, misses=m, dram_bytes=d)
