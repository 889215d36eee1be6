"""The block-table dispatch seam for paged KV caches.

Every place the model layer touches K/V through a block table funnels
through this module: the tail-block scatter of a decode step, the
page gather that reconstructs a sequence in position order, and the
prefix gather used by chunked/prefix-extend prefill.  `attention.py`,
`mla.py` and `transformer.py` contain no block-table arithmetic of
their own — they ask this seam for position-ordered K/V and write
refs, which is what keeps the paged paths bit-identical to the
contiguous ones (a gather in position order IS the contiguous row).

``PagedPrefix`` / ``SlotPrefix`` name the two cache layouts a
prefix-extend prefill can read its prefix from: a block-pool arena
reached through a block table, or a contiguous slot row.  They are
constructed inside jitted step functions from plain array arguments,
so they never cross a jit boundary themselves.
"""
from __future__ import annotations

import dataclasses
from typing import Tuple, Union

import jax
import jax.numpy as jnp


@dataclasses.dataclass(frozen=True)
class PagedPrefix:
    """Prefix K/V lives in a paged arena, reached via ``block_tables``
    ([B, P] int32); ``block_size`` is static."""
    block_tables: jax.Array
    block_size: int


@dataclasses.dataclass(frozen=True)
class SlotPrefix:
    """Prefix K/V lives in contiguous slot rows ``slots`` ([B] int32) of
    a ``[num_slots, max_len, ...]`` cache."""
    slots: jax.Array


PrefixRef = Union[PagedPrefix, SlotPrefix]


def tail_refs(block_tables: jax.Array, pos: jax.Array,
              block_size: int) -> Tuple[jax.Array, jax.Array]:
    """(block ids, in-block offsets) of each row's write position(s).

    ``pos`` is [B] (one write per row — plain decode) or [B, S']
    (speculative verify: S' consecutive write positions per row).  Rows
    whose table entry is the trash block 0 (inactive slots, padding)
    resolve to block 0 — writes there are harmless and reads from it are
    always masked."""
    rows = jnp.arange(pos.shape[0])
    if pos.ndim == 2:
        rows = rows[:, None]
    return block_tables[rows, pos // block_size], pos % block_size


def scatter_token(leaf: jax.Array, blk: jax.Array, off: jax.Array,
                  new: jax.Array) -> jax.Array:
    """Write new cache entries into their tail blocks.  ``blk``/``off``
    are [B] with ``new`` [B, ...] (one token per row), or [B, S'] with
    ``new`` [B, S', ...] (a speculative verify window)."""
    return leaf.at[blk, off].set(new.astype(leaf.dtype))


def gather_pages(leaf: jax.Array, block_tables: jax.Array) -> jax.Array:
    """Reassemble each row's sequence in position order: [B, P*bs, ...].

    This reconstructs exactly the contiguous cache row (pages are
    gathered in table order and the table is position-ordered), which
    is the bit-identity argument for paged decode."""
    B, P = block_tables.shape
    bs = leaf.shape[1]
    return leaf[block_tables].reshape((B, P * bs) + leaf.shape[2:])


def valid_mask(total_len: int, pos: jax.Array) -> jax.Array:
    """[B, T] mask of cache positions at or before each row's write
    position (position ``pos`` itself was just written this step)."""
    return jnp.arange(total_len)[None, :] <= pos[:, None]


def use_fused_decode(cfg, flags) -> bool:
    """Should this attention layer's decode/verify step run through the
    fused flash-decode kernel (``kernels.flash_decode``)?

    The ONE predicate `attention.py` consults before deciding whether to
    rotate q/k outside the kernel: the fused path wants them un-rotated.
    Sliding-window layers keep the wraparound slot layout (positions are
    not monotone in the cache, so a position-ordered arena view does not
    exist) and multi-host decode keeps the sharded-gather path.  MLA
    never reaches here — its latent cache decodes in ``mla.py``.

    Tensor-parallel serving (``flags.decode_shards`` > 1,
    docs/SHARDING.md): the kernel runs under ``shard_map`` with per-rank
    K/V head slices, which needs the kv heads to divide the model axis
    (GQA groups then stay rank-local: heads ``[r*H/m, (r+1)*H/m)`` read
    exactly kv heads ``[r*KV/m, (r+1)*KV/m)``).  Indivisible head counts
    fall back to the gather path, which GSPMD partitions on its own."""
    shards = getattr(flags, "decode_shards", 1) if flags is not None else 1
    return (flags is not None
            and getattr(flags, "use_fused_decode", False)
            and not cfg.sliding_window
            and getattr(flags, "model_size", 1) == 1
            and (shards == 1 or cfg.num_kv_heads % shards == 0))


def use_paged_attention(cfg, flags) -> bool:
    """Should a paged single-token decode step attend through the Pallas
    paged-attention kernel (``kernels.paged_attention``)?

    It is the default for every GQA/MHA/MQA layer on a paged arena
    (MLA decodes its latent cache in ``mla.py``, and a paged arena has
    no sliding window), asked only once the fused path has declined.
    Under tensor-parallel serving the kernel runs under ``shard_map``
    on each rank's kv heads, so the kv heads must divide the decode
    shards; otherwise the page gather, which GSPMD partitions on its
    own, attends.  Speculative windows always take the gather."""
    shards = getattr(flags, "decode_shards", 1) if flags is not None else 1
    return shards == 1 or cfg.num_kv_heads % shards == 0


def fused_page_size(max_len: int, preferred: int = 8) -> int:
    """Page granularity for viewing a contiguous slot row as an arena.

    ``preferred`` matches the serving default block size so the slot and
    paged layouts accumulate split-K partials over identical page
    boundaries (bit-identical tokens across layouts); rows whose length
    is not a multiple fall back to one whole-row page."""
    return preferred if max_len % preferred == 0 else max_len


def slot_arena_tables(batch: int, max_len: int, page: int) -> jax.Array:
    """Block tables presenting a contiguous ``[N, max_len, ...]`` slot
    cache (reshaped to ``[N * (max_len // page), page, ...]``) as a
    position-ordered arena: row ``b``'s page ``p`` is block
    ``b * P + p``.  Every block is real — there is no trash block, and
    the fused kernel's page write-back is idempotent for pages outside
    the write window, so none is needed."""
    P = max_len // page
    return (jnp.arange(batch, dtype=jnp.int32)[:, None] * P
            + jnp.arange(P, dtype=jnp.int32)[None, :])


def gather_prefix_kv(mixer_cache, ref: PrefixRef, prefix_len: int):
    """Gather positions ``[0, prefix_len)`` of each row's cached K/V.

    The ONE place prefix-extend prefill dispatches on cache layout:
    paged gathers ``prefix_len // block_size`` whole pages through the
    table; slot slices the head of the contiguous row."""
    if isinstance(ref, SlotPrefix):
        return jax.tree.map(lambda a: a[ref.slots, :prefix_len],
                            mixer_cache)
    n_pages = prefix_len // ref.block_size
    ptbl = ref.block_tables[:, :n_pages]
    B = ref.block_tables.shape[0]
    return jax.tree.map(
        lambda a: a[ptbl].reshape((B, prefix_len) + a.shape[2:]),
        mixer_cache)
