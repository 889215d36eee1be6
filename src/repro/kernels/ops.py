"""Jit'd public wrappers for the Pallas kernels.

Each wrapper lowers its kernel per platform: through the Pallas
interpreter when the program is lowered for the CPU (where Mosaic does
not exist, and where the tests check the kernels against ``ref.py``),
and through Mosaic for every other platform.  The choice is made by
``jax.lax.platform_dependent`` at lowering time, so a TPU never runs a
kernel interpreted and nothing outside the program can switch it.
"""
from __future__ import annotations

from functools import partial

import jax
import jax.numpy as jnp

from .flash_attention import flash_attention_kernel
from .flash_decode import fused_flash_decode_kernel
from .paged_attention import paged_attention_kernel
from .rmsnorm import rmsnorm_kernel


def _per_platform(kernel, *args, **kw):
    """Call ``kernel(*args, **kw)`` interpreted on the CPU and compiled
    everywhere else."""
    return jax.lax.platform_dependent(
        *args,
        cpu=partial(kernel, interpret=True, **kw),
        default=partial(kernel, interpret=False, **kw))


@partial(jax.jit, static_argnames=("causal", "window", "q_offset"))
def flash_attention(q: jax.Array, k: jax.Array, v: jax.Array, *,
                    causal: bool = True, window: int = 0,
                    q_offset: int = 0) -> jax.Array:
    return _per_platform(flash_attention_kernel, q, k, v, causal=causal,
                         window=window, q_offset=q_offset)


@partial(jax.jit, static_argnames=("eps",))
def rmsnorm(x: jax.Array, scale: jax.Array, *, eps: float = 1e-5) -> jax.Array:
    return _per_platform(rmsnorm_kernel, x, scale, eps=eps)


@partial(jax.jit, static_argnames=("rope_theta", "split_k"))
def fused_flash_decode(q: jax.Array, k_new: jax.Array, v_new: jax.Array,
                       k_pages: jax.Array, v_pages: jax.Array,
                       block_tables: jax.Array, positions: jax.Array, *,
                       rope_theta: float = 10_000.0, split_k: bool = False):
    """One-call fused decode/verify attention: RoPE + tail-block scatter
    + per-query-masked attention over the paged arena (see
    repro.kernels.flash_decode).  Returns (out, k_pages, v_pages)."""
    return _per_platform(fused_flash_decode_kernel, q, k_new, v_new,
                         k_pages, v_pages,
                         jnp.asarray(block_tables, jnp.int32),
                         jnp.asarray(positions, jnp.int32),
                         rope_theta=rope_theta, split_k=split_k)


@jax.jit
def paged_attention(q: jax.Array, k_pages: jax.Array, v_pages: jax.Array,
                    block_tables: jax.Array,
                    positions: jax.Array) -> jax.Array:
    """Paged decode attention through block tables (see
    repro.kernels.paged_attention)."""
    return _per_platform(paged_attention_kernel, q, k_pages, v_pages,
                         block_tables, positions)
