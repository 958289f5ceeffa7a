"""Jitted public wrappers for the Pallas kernels.

On this CPU-only container kernels run in interpret mode (the kernel body is
executed with JAX ops — bit-exact semantics, no TPU). On a TPU runtime set
``interpret=False`` (the default flips automatically via `on_tpu()`).
"""
from __future__ import annotations

import functools

import jax

from . import flash_attention, paged_attention, ref


def on_tpu() -> bool:
    return jax.default_backend() == "tpu"


def _interp(interpret):
    return (not on_tpu()) if interpret is None else interpret


@functools.partial(jax.jit, static_argnames=("interpret",))
def paged_attention_op(q, k_pages, v_pages, page_table, seq_lens, *,
                       interpret: bool | None = None):
    return paged_attention.paged_attention_kernel(
        q, k_pages, v_pages, page_table, seq_lens, interpret=_interp(interpret)
    )


# re-exported oracles for tests/benchmarks
paged_attention_ref = ref.paged_attention_ref


@functools.partial(jax.jit, static_argnames=("causal", "window", "block_q",
                                              "block_kv", "interpret"))
def flash_attention_op(q, k, v, *, causal: bool = True, window: int = 0,
                       block_q: int = 512, block_kv: int = 512,
                       interpret: bool | None = None):
    """Pallas flash attention (fwd). Ref oracle: layers.attention."""
    return flash_attention.flash_attention_kernel(
        q, k, v, causal=causal, window=window, block_q=block_q,
        block_kv=block_kv, interpret=_interp(interpret))
