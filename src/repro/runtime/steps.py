"""Training and serving step factories.

These are the functions the launcher jits/shards and the dry-run lowers:

* ``train_step(state, batch) -> (state, metrics)``   — loss + grad + optimizer
* ``prefill_step(params, batch) -> (tokens, cache)`` — prompt ingestion
* ``decode_step(params, tokens, cache, pos) -> (tokens, cache)`` — one token
"""
from __future__ import annotations

import dataclasses
from functools import partial
from typing import Any, Callable, Dict, NamedTuple, Optional

import jax
import jax.numpy as jnp
from jax import lax
from jax.tree_util import tree_map_with_path

from ..models.model import Model
from ..models.transformer import DEFAULT_FLAGS, RuntimeFlags
from ..optim import make_optimizer
from ..optim.optimizers import OptState


class TrainState(NamedTuple):
    params: Any
    opt: OptState


def cross_entropy(logits: jax.Array, labels: jax.Array,
                  mask: Optional[jax.Array] = None) -> jax.Array:
    """Mean CE in fp32. logits [B,S,V], labels [B,S]."""
    logits = logits.astype(jnp.float32)
    lse = jax.nn.logsumexp(logits, axis=-1)
    gold = jnp.take_along_axis(logits, labels[..., None], axis=-1)[..., 0]
    nll = lse - gold
    if mask is not None:
        mask = mask.astype(jnp.float32)
        return (nll * mask).sum() / jnp.maximum(mask.sum(), 1.0)
    return nll.mean()


def make_train_step(model: Model, *, schedule: Callable[[jax.Array], jax.Array],
                    flags: RuntimeFlags = DEFAULT_FLAGS,
                    optimizer: Optional[str] = None):
    cfg = model.cfg
    opt_init, opt_update = make_optimizer(optimizer or cfg.optimizer)

    def loss_fn(params, batch):
        kw = {}
        if "prefix_embeds" in batch:
            kw["prefix_embeds"] = batch["prefix_embeds"]
        if "enc_embeds" in batch:
            kw["enc_embeds"] = batch["enc_embeds"]
        logits, aux, hidden = model.forward(params, batch["tokens"], flags=flags, **kw)
        labels = batch["labels"]
        mask = None
        if "prefix_embeds" in batch:
            P = batch["prefix_embeds"].shape[1]
            pos = jnp.arange(labels.shape[1])
            mask = jnp.broadcast_to(pos >= P, labels.shape)
        ce = cross_entropy(logits, labels, mask)
        loss = ce + cfg.router_aux_weight * aux
        metrics = {"loss": ce, "aux": aux}
        if cfg.mtp_depth:
            # MTP: predict token t+2 from hidden_t (+ embed of t+1)
            mtp = model.mtp_logits(params, hidden, batch["tokens"], flags=flags)
            mtp_labels = jnp.concatenate(
                [labels[:, 1:], labels[:, -1:]], axis=1)
            mtp_loss = cross_entropy(mtp, mtp_labels, mask)
            loss = loss + 0.3 * mtp_loss
            metrics["mtp_loss"] = mtp_loss
        return loss, metrics

    def train_step(state: TrainState, batch) -> tuple:
        (loss, metrics), grads = jax.value_and_grad(
            loss_fn, has_aux=True)(state.params, batch)
        lr = schedule(state.opt.step + 1)
        new_params, new_opt = opt_update(grads, state.opt, state.params, lr)
        # NOTE: jnp.vdot flattens each grad -> a reshape of a 2-D-sharded
        # tensor -> XLA materializes a full fp32 all-gather (4.8 TiB/device
        # on deepseek-v3).  Elementwise square + reduce shards cleanly.
        gnorm = jnp.sqrt(sum(
            jnp.sum(jnp.square(g.astype(jnp.float32)))
            for g in jax.tree.leaves(grads)))
        metrics = dict(metrics, total_loss=loss, lr=lr, grad_norm=gnorm)
        return TrainState(new_params, new_opt), metrics

    def init_state(params) -> TrainState:
        return TrainState(params, opt_init(params))

    return train_step, init_state


def make_prefill_step(model: Model, max_cache_len: int,
                      flags: RuntimeFlags = DEFAULT_FLAGS):
    def prefill_step(params, batch):
        kw = {}
        if "prefix_embeds" in batch:
            kw["prefix_embeds"] = batch["prefix_embeds"]
        if "enc_embeds" in batch:
            kw["enc_embeds"] = batch["enc_embeds"]
        logits, cache = model.prefill(params, batch["tokens"],
                                      max_cache_len, flags=flags, **kw)
        next_tok = jnp.argmax(logits, axis=-1).astype(jnp.int32)
        return next_tok, cache

    return prefill_step


def make_decode_step(model: Model, flags: RuntimeFlags = DEFAULT_FLAGS):
    def decode_step(params, tokens, cache, cache_pos):
        logits, new_cache = model.decode_step(params, tokens, cache,
                                              cache_pos, flags=flags)
        next_tok = jnp.argmax(logits, axis=-1).astype(jnp.int32)[:, None]
        return next_tok, new_cache

    return decode_step


def kernel_path(cfg, flags: RuntimeFlags, backend_kind: str = "slot",
                step: str = "decode") -> str:
    """Which decode-attention implementation a serving step will run:
    ``"fused"`` (the fused flash-decode Pallas kernel), ``"paged"`` (the
    paged-attention Pallas kernel: single-token decode on a paged or
    hybrid arena) or ``"fallback"`` (page gather / masked contiguous
    attention).

    The observability face of the dispatch seam: LLMEngine labels its
    ``engine.kernel_path`` counter with this, so a silent fall-off the
    kernels (MLA, sliding window, a speculative window on the gather,
    a recurrent-only stack) shows up in ``metrics_text()`` instead of
    only in throughput."""
    from ..models import paging
    if getattr(cfg, "use_mla", False):
        return "fallback"          # latent cache decodes in mla.py
    if "attn" not in cfg.layer_kinds():
        return "fallback"          # recurrent-only stack: nothing to fuse
    if paging.use_fused_decode(cfg, flags):
        return "fused"
    if (backend_kind in ("paged", "hybrid") and step == "decode"
            and paging.use_paged_attention(cfg, flags)):
        return "paged"
    return "fallback"


def make_serve_decode_step(model: Model,
                           flags: RuntimeFlags = DEFAULT_FLAGS,
                           pad_id: int = 0, paged: bool = False,
                           masked_state: bool = False):
    """Decode one token for every *slot* of a continuous batch.

    Unlike :func:`make_decode_step`, the batch rows are independent
    in-flight requests: ``positions`` is a [N] vector of per-slot cache
    offsets and ``active`` a [N] bool mask of occupied slots.  Inactive
    slots still flow through the computation (the batch shape is static so
    the step compiles once — every row op is row-independent, so they
    cannot perturb active rows, and a later insert overwrites the whole
    cache row anyway) but their emitted token is forced to ``pad_id`` so
    the host scheduler can ignore them.

    With ``paged=True`` the cache is a paged block-pool arena and each
    slot reaches its K/V through a block table ([N, P] int32; inactive
    rows hold all-zero tables, so their writes land in the trash
    block 0).  One factory serves both cache layouts — the layout
    difference is entirely inside the model's block-table seam
    (:mod:`repro.models.paging`).

    ``masked_state=True`` (the state/hybrid layouts) additionally passes
    ``active`` as the model's ``state_mask``: recurrent mixers overwrite
    their whole O(1) state every step, so without the mask a decode tick
    would destroy the checkpointed ingest-frontier state of rows that
    are mid-chunked-prefill.
    """
    def mask_tok(logits, active):
        return jnp.where(
            active[:, None],
            jnp.argmax(logits, axis=-1).astype(jnp.int32)[:, None],
            jnp.asarray(pad_id, jnp.int32))

    if paged:
        def paged_decode_step(params, tokens, cache, positions, active,
                              block_tables):
            logits, new_cache = model.decode_step(
                params, tokens, cache, positions, flags=flags,
                block_tables=block_tables,
                state_mask=active if masked_state else None)
            return mask_tok(logits, active), new_cache
        return paged_decode_step

    def slot_decode_step(params, tokens, cache, positions, active):
        logits, new_cache = model.decode_step(
            params, tokens, cache, positions, flags=flags,
            state_mask=active if masked_state else None)
        return mask_tok(logits, active), new_cache

    return slot_decode_step


def make_verify_step(model: Model,
                     flags: RuntimeFlags = DEFAULT_FLAGS,
                     pad_id: int = 0, paged: bool = False):
    """Speculative verification: score a ``[N, 1+k]`` token window per
    slot — each row's last emitted token followed by ``k`` drafted
    tokens — in ONE forward pass, and return the greedy argmax at every
    window position (``[N, 1+k]`` int32; inactive rows forced to
    ``pad_id``).

    This is :func:`make_serve_decode_step` generalized from one query
    token to a window: K/V for all ``1+k`` tokens is written at
    positions ``pos..pos+k`` of each row, and window token ``s`` attends
    under the causal mask ``idx <= pos + s`` — exactly what ``1+k``
    successive one-token decode steps would compute, which is why the
    host can accept the longest drafted prefix matching the argmax chain
    and stay bit-identical to plain greedy decode
    (docs/SPECULATIVE.md).  Rejected tail writes are rolled back by the
    scheduler/backend (``positions`` rewind + paged ``truncate``).

    With ``k = 0`` the window degenerates to the plain serve decode
    step; the scheduler uses that path directly so non-speculative
    serving never pays the generalization."""
    def mask_tok(logits, active):
        return jnp.where(
            active[:, None],
            jnp.argmax(logits, axis=-1).astype(jnp.int32),
            jnp.asarray(pad_id, jnp.int32))

    if paged:
        def paged_verify_step(params, tokens, cache, positions, active,
                              block_tables):
            logits, new_cache = model.decode_step(
                params, tokens, cache, positions, flags=flags,
                block_tables=block_tables, all_logits=True)
            return mask_tok(logits, active), new_cache
        return paged_verify_step

    def slot_verify_step(params, tokens, cache, positions, active):
        logits, new_cache = model.decode_step(params, tokens, cache,
                                              positions, flags=flags,
                                              all_logits=True)
        return mask_tok(logits, active), new_cache

    return slot_verify_step


# ---------------------------------------------------------------------------
# cache-row insert / extend (continuous batching)
# ---------------------------------------------------------------------------

def slot_batch_axis(path) -> int:
    """Axis of the slot (batch) dimension in a cache leaf.

    ``prefill`` returns head-layer leaves shaped [B, ...] and scanned-block
    leaves shaped [R, B, ...] (R = layer-group repeat count), so the batch
    axis is 1 under the top-level ``"blocks"`` key and 0 everywhere else.
    """
    return 1 if (path and getattr(path[0], "key", None) == "blocks") else 0


def make_slot_insert():
    """Build ``insert(cache, rows, row, slot)``: copy cache row ``row`` of a
    freshly prefilled batch into slot ``slot`` of the persistent slot cache.
    ``row``/``slot`` are traced scalars, so one compilation covers every
    slot index (recompiles only on a new prefill batch width)."""

    def insert(cache, rows, row, slot):
        def ins(path, big, rs):
            ax = slot_batch_axis(path)
            r = lax.dynamic_slice_in_dim(rs, row, 1, axis=ax)
            return lax.dynamic_update_slice_in_dim(
                big, r.astype(big.dtype), slot, axis=ax)

        return tree_map_with_path(ins, cache, rows)

    return insert


def _paged_scatter_rows(block_size: int, arena, rows, row, page_ids):
    """Scatter one prefilled cache row (``[B, S_cache, ...]``, ``S_cache``
    a multiple of ``block_size``) into the paged arena, page by page.

    ``page_ids`` is a fixed-length [P] int32 vector — entry ``j`` is the
    arena block receiving the row's ``j``-th page, or 0 (the trash block)
    for pages that must not land anywhere: padding beyond the prompt, and
    pages whose content is already present as a shared prefix block
    (shared blocks are immutable — redirecting their writes to the trash
    block preserves that invariant).  Fixed length means one compilation
    covers every page count."""
    def ins(path, big, rs):
        ax = slot_batch_axis(path)
        r = lax.dynamic_slice_in_dim(rs, row, 1, axis=ax)
        r = lax.squeeze(r, (ax,))
        if ax == 1:                     # scanned blocks: [R, S, ...]
            R_, S = r.shape[0], r.shape[1]
            pages = r.reshape((R_, S // block_size, block_size)
                              + r.shape[2:])
            return big.at[:, page_ids].set(pages.astype(big.dtype))
        S = r.shape[0]                   # head layers: [S, ...]
        pages = r.reshape((S // block_size, block_size) + r.shape[1:])
        return big.at[page_ids].set(pages.astype(big.dtype))

    return tree_map_with_path(ins, arena, rows)


def make_paged_insert(block_size: int):
    """Build ``insert(arena, rows, row, page_ids)`` — see
    :func:`_paged_scatter_rows`."""
    return partial(_paged_scatter_rows, block_size)


def _slot_write_rows(cache, rows, slot, offset: int):
    """Write batch-1 suffix rows (seq length S', unpadded) into slot
    ``slot`` at sequence offset ``offset`` (static) — the chunked-prefill
    insert for the contiguous layout."""
    def ins(path, big, rs):
        ax = slot_batch_axis(path)
        r = lax.dynamic_slice_in_dim(rs, 0, 1, axis=ax)   # row 0 of [.,1,.]
        starts = [jnp.asarray(0, jnp.int32)] * big.ndim
        starts[ax] = jnp.asarray(slot, jnp.int32)
        starts[ax + 1] = jnp.asarray(offset, jnp.int32)
        return lax.dynamic_update_slice(big, r.astype(big.dtype), starts)

    return tree_map_with_path(ins, cache, rows)


def make_extend_step(model: Model, prefix_len: int,
                     flags: RuntimeFlags = DEFAULT_FLAGS, *,
                     block_size: int = 0, max_cache_len: int = 0):
    """Chunked / prefix-shared prefill: compute only a prompt suffix
    against the request's cached prefix, write the suffix K/V back into
    its cache, and return the last position's next token (meaningful only
    when the suffix ends the prompt).  ``prefix_len`` is static — one
    compiled step per (prefix length, suffix length) shape pair.

    ``block_size == 0`` builds the slot-layout step
    ``(params, tokens [1,S'], cache, slot) -> (tok [1], cache)`` that
    reads the prefix from — and writes the suffix into — contiguous slot
    row ``slot``; otherwise the paged step
    ``(params, tokens [1,S'], cache, table_row [P], page_ids [P]) ->
    (tok [1], cache)`` reads prefix pages through ``table_row`` and
    scatters suffix pages to the ``page_ids`` blocks."""
    from ..models.paging import PagedPrefix, SlotPrefix

    if block_size:
        if max_cache_len <= 0:
            raise ValueError("paged extend step needs max_cache_len "
                             "(rows must pad to whole pages)")
        def paged_extend_step(params, tokens, cache, table_row, page_ids):
            ref = PagedPrefix(table_row[None], block_size)
            logits, rows = model.prefill_extend(
                params, tokens, cache, ref, prefix_len,
                max_cache_len, flags=flags)
            cache = _paged_scatter_rows(block_size, cache, rows,
                                        jnp.asarray(0, jnp.int32), page_ids)
            next_tok = jnp.argmax(logits, axis=-1).astype(jnp.int32)
            return next_tok, cache
        return paged_extend_step

    def slot_extend_step(params, tokens, cache, slot):
        ref = SlotPrefix(slot[None])
        # max_cache_len == suffix length: rows come back unpadded, so the
        # in-place write touches exactly [slot, prefix_len:prefix_len+S')
        logits, rows = model.prefill_extend(
            params, tokens, cache, ref, prefix_len,
            tokens.shape[1], flags=flags)
        cache = _slot_write_rows(cache, rows, slot, prefix_len)
        next_tok = jnp.argmax(logits, axis=-1).astype(jnp.int32)
        return next_tok, cache

    return slot_extend_step


# ---------------------------------------------------------------------------
# state / hybrid layouts (recurrent mixers in O(1) state slabs)
# ---------------------------------------------------------------------------

RECURRENT_KINDS = ("mamba", "mlstm", "slstm")


def make_state_verify_step(model: Model,
                           flags: RuntimeFlags = DEFAULT_FLAGS,
                           pad_id: int = 0, paged: bool = False):
    """:func:`make_verify_step` for the state/hybrid layouts.

    Recurrent state cannot be rolled back by rewinding a position
    counter, so the window pass leaves every state slab *uncommitted*
    and additionally returns per-position state stacks (the state after
    each window token, for every slot); the backend's ``truncate``
    commits the accepted prefix's entry via :func:`make_state_rewind`.
    Attention arenas (hybrid) are written as usual — their rejected
    tail rolls back by position rewind / page truncate exactly as on
    the paged layout.  Returns ``(guess [N,1+k], new_cache, stacks)``.
    """
    def mask_tok(logits, active):
        return jnp.where(
            active[:, None],
            jnp.argmax(logits, axis=-1).astype(jnp.int32),
            jnp.asarray(pad_id, jnp.int32))

    if paged:
        def hybrid_verify_step(params, tokens, cache, positions, active,
                               block_tables):
            logits, new_cache, stacks = model.decode_step(
                params, tokens, cache, positions, flags=flags,
                block_tables=block_tables, all_logits=True,
                want_state_stacks=True)
            return mask_tok(logits, active), new_cache, stacks
        return hybrid_verify_step

    def state_verify_step(params, tokens, cache, positions, active):
        logits, new_cache, stacks = model.decode_step(
            params, tokens, cache, positions, flags=flags,
            all_logits=True, want_state_stacks=True)
        return mask_tok(logits, active), new_cache, stacks

    return state_verify_step


def make_state_rewind(model: Model):
    """Build ``rewind(cache, stacks, slot, idx)``: commit the state after
    window position ``idx`` (0-based within the verify window) of row
    ``slot`` from the stacks a state verify step returned.  State-slab
    leaves take ``stack[slot, idx]``; every other leaf (attention arenas
    carry zero-size placeholders in the stacks) passes through
    untouched.  ``slot``/``idx`` are traced, so one compilation covers
    every accept length of every slot at a given window width."""
    def rewind(cache, stacks, slot, idx):
        def sel(path, live, stk):
            if stk.size == 0:
                return live
            ax = slot_batch_axis(path)
            if ax == 1:                  # scanned blocks: [R, N, L, ...]
                return live.at[:, slot].set(
                    stk[:, slot, idx].astype(live.dtype))
            return live.at[slot].set(stk[slot, idx].astype(live.dtype))

        return tree_map_with_path(sel, cache, stacks)

    return rewind


def _state_write_rows(model: Model, cache, rows, slot, offset: int):
    """Chunked-prefill write-back on the state layout: attention leaves
    (mixed stacks keep contiguous slot rows here) write suffix rows at
    ``[slot, offset:offset+S')``; recurrent leaves overwrite slab row
    ``slot`` with the final state after the chunk — the slab row IS the
    ingest-frontier checkpoint."""
    def ins(path, big, rs):
        ax = slot_batch_axis(path)
        kind = model.layer_kind_of_path(path)
        r = lax.dynamic_slice_in_dim(rs, 0, 1, axis=ax)
        starts = [jnp.asarray(0, jnp.int32)] * big.ndim
        starts[ax] = jnp.asarray(slot, jnp.int32)
        if kind == "attn":
            starts[ax + 1] = jnp.asarray(offset, jnp.int32)
        return lax.dynamic_update_slice(big, r.astype(big.dtype), starts)

    return tree_map_with_path(ins, cache, rows)


def _hybrid_scatter_rows(model: Model, block_size: int, arena, rows, row,
                         page_ids, slot):
    """Hybrid-layout cache write: attention leaves scatter the row's
    pages to the ``page_ids`` blocks (see :func:`_paged_scatter_rows`);
    recurrent leaves copy batch row ``row`` of the prefilled states into
    slab row ``slot``."""
    def ins(path, big, rs):
        ax = slot_batch_axis(path)
        kind = model.layer_kind_of_path(path)
        r = lax.dynamic_slice_in_dim(rs, row, 1, axis=ax)
        if kind == "attn":
            r = lax.squeeze(r, (ax,))
            if ax == 1:                 # scanned blocks: [R, S, ...]
                R_, S = r.shape[0], r.shape[1]
                pages = r.reshape((R_, S // block_size, block_size)
                                  + r.shape[2:])
                return big.at[:, page_ids].set(pages.astype(big.dtype))
            S = r.shape[0]               # head layers: [S, ...]
            pages = r.reshape((S // block_size, block_size) + r.shape[1:])
            return big.at[page_ids].set(pages.astype(big.dtype))
        starts = [jnp.asarray(0, jnp.int32)] * big.ndim
        starts[ax] = jnp.asarray(slot, jnp.int32)
        return lax.dynamic_update_slice(big, r.astype(big.dtype), starts)

    return tree_map_with_path(ins, arena, rows)


def make_hybrid_insert(model: Model, block_size: int):
    """Build ``insert(arena, rows, row, page_ids, slot)`` — see
    :func:`_hybrid_scatter_rows`."""
    return partial(_hybrid_scatter_rows, model, block_size)


def make_state_extend_step(model: Model, prefix_len: int,
                           flags: RuntimeFlags = DEFAULT_FLAGS, *,
                           block_size: int = 0, max_cache_len: int = 0):
    """:func:`make_extend_step` for the state/hybrid layouts: attention
    layers extend against gathered prefix K/V exactly as before, while
    recurrent layers *continue the sequential state scan* from their
    slab row — so the state after chunk k is bit-identical to a cold
    prefill of ``prompt[:end_k]``, whatever the chunk boundaries.

    ``block_size == 0`` builds the state-layout step
    ``(params, tokens [1,S'], cache, slot) -> (tok [1], cache)``;
    otherwise the hybrid step ``(params, tokens [1,S'], cache,
    table_row [P], page_ids [P], slot) -> (tok [1], cache)``."""
    from ..models.paging import PagedPrefix, SlotPrefix

    if block_size:
        if max_cache_len <= 0:
            raise ValueError("hybrid extend step needs max_cache_len "
                             "(attention rows must pad to whole pages)")
        def hybrid_extend_step(params, tokens, cache, table_row, page_ids,
                               slot):
            ref = PagedPrefix(table_row[None], block_size)
            logits, rows = model.prefill_extend(
                params, tokens, cache, ref, prefix_len,
                max_cache_len, flags=flags, slots=slot[None])
            cache = _hybrid_scatter_rows(model, block_size, cache, rows,
                                         jnp.asarray(0, jnp.int32),
                                         page_ids, slot)
            next_tok = jnp.argmax(logits, axis=-1).astype(jnp.int32)
            return next_tok, cache
        return hybrid_extend_step

    def state_extend_step(params, tokens, cache, slot):
        ref = SlotPrefix(slot[None])
        logits, rows = model.prefill_extend(
            params, tokens, cache, ref, prefix_len,
            tokens.shape[1], flags=flags, slots=slot[None])
        cache = _state_write_rows(model, cache, rows, slot, prefix_len)
        next_tok = jnp.argmax(logits, axis=-1).astype(jnp.int32)
        return next_tok, cache

    return state_extend_step
