"""GQA attention with RoPE, optional qk-norm (qwen3), sliding windows, and
a KV cache for decode.  Pure functions; the Pallas flash kernel is an
optional drop-in for the prefill/train path (see repro.kernels).
"""
from __future__ import annotations

from functools import partial
from typing import Dict, Optional, Tuple

import jax
import jax.numpy as jnp

from . import paging
from .config import ArchConfig
from .layers import apply_rope, rms_norm
from .params import ParamSpec, Template

NEG_INF = -1e30


def attention_template(cfg: ArchConfig) -> Template:
    d, hd = cfg.d_model, cfg.head_dim
    t: Template = {
        "wq": ParamSpec((d, cfg.num_heads, hd), ("embed", "heads", "head_dim")),
        "wk": ParamSpec((d, cfg.num_kv_heads, hd), ("embed", "kv_heads", "head_dim")),
        "wv": ParamSpec((d, cfg.num_kv_heads, hd), ("embed", "kv_heads", "head_dim")),
        "wo": ParamSpec((cfg.num_heads, hd, d), ("heads", "head_dim", "embed")),
    }
    if cfg.qk_norm:
        t["q_norm"] = {"scale": ParamSpec((hd,), ("head_dim",), init="ones")}
        t["k_norm"] = {"scale": ParamSpec((hd,), ("head_dim",), init="ones")}
    return t


def init_kv_cache(cfg: ArchConfig, batch: int, max_len: int,
                  dtype) -> Dict[str, jax.Array]:
    window = cfg.sliding_window or 0
    size = min(max_len, window) if window else max_len
    shape = (batch, size, cfg.num_kv_heads, cfg.head_dim)
    return {"k": jnp.zeros(shape, dtype), "v": jnp.zeros(shape, dtype)}


def abstract_kv_cache(cfg: ArchConfig, batch: int, max_len: int, dtype):
    window = cfg.sliding_window or 0
    size = min(max_len, window) if window else max_len
    shape = (batch, size, cfg.num_kv_heads, cfg.head_dim)
    return {"k": jax.ShapeDtypeStruct(shape, jnp.dtype(dtype)),
            "v": jax.ShapeDtypeStruct(shape, jnp.dtype(dtype))}


def abstract_paged_kv_cache(cfg: ArchConfig, num_blocks: int,
                            block_size: int, dtype):
    """Paged arena: the slot/sequence axis is replaced by a pool of
    fixed-size token blocks shared by all sequences (block 0 = trash)."""
    shape = (num_blocks, block_size, cfg.num_kv_heads, cfg.head_dim)
    return {"k": jax.ShapeDtypeStruct(shape, jnp.dtype(dtype)),
            "v": jax.ShapeDtypeStruct(shape, jnp.dtype(dtype))}


def _qkv(params, cfg: ArchConfig, x: jax.Array, positions: jax.Array,
         rope: bool = True):
    """Project q/k/v (+ qk-norm).  ``rope=False`` returns un-rotated q/k
    for the fused decode path, which applies the (bitwise identical)
    rotation inside the kernel at the same positions."""
    q = jnp.einsum("bsd,dhk->bshk", x, params["wq"])
    k = jnp.einsum("bsd,dhk->bshk", x, params["wk"])
    v = jnp.einsum("bsd,dhk->bshk", x, params["wv"])
    if cfg.qk_norm:
        q = rms_norm(params["q_norm"], q, cfg.norm_eps)
        k = rms_norm(params["k_norm"], k, cfg.norm_eps)
    if rope:
        q = apply_rope(q, positions, cfg.rope_theta)
        k = apply_rope(k, positions, cfg.rope_theta)
    return q, k, v


def _grouped_attention(q: jax.Array, k: jax.Array, v: jax.Array,
                       mask: jax.Array) -> jax.Array:
    """q: [B,S,H,hd], k/v: [B,T,KV,hd], mask: [B,1,1,S,T] or broadcastable.
    Grouped einsum avoids materializing repeated KV heads."""
    B, S, H, hd = q.shape
    KV = k.shape[2]
    G = H // KV
    qg = q.reshape(B, S, KV, G, hd)
    scores = jnp.einsum("bskgd,btkd->bkgst", qg, k).astype(jnp.float32)
    scores = scores / jnp.sqrt(hd).astype(jnp.float32)
    scores = jnp.where(mask, scores, NEG_INF)
    probs = jax.nn.softmax(scores, axis=-1).astype(q.dtype)
    out = jnp.einsum("bkgst,btkd->bskgd", probs, v)
    return out.reshape(B, S, H, hd)


def causal_mask(seq: int, window: int = 0) -> jax.Array:
    i = jnp.arange(seq)[:, None]
    j = jnp.arange(seq)[None, :]
    m = j <= i
    if window:
        m = m & (j > i - window)
    return m[None, None, None]      # [1,1,1,S,T]


def _seq_attention(q, k, v, cfg: ArchConfig, impl: str,
                   flags=None) -> jax.Array:
    """Dispatch over attention implementations for full-sequence paths."""
    if impl == "flash":
        from ..kernels.ops import flash_attention
        return flash_attention(q, k, v, causal=True,
                               window=cfg.sliding_window)
    if impl == "chunked":
        from .chunked_attention import (chunked_attention,
                                        sequence_parallel_attention)
        if flags is not None and getattr(flags, "model_size", 1) > 1:
            return sequence_parallel_attention(
                q, k, v, causal=True, window=cfg.sliding_window,
                flags=flags)
        return chunked_attention(q, k, v, causal=True,
                                 window=cfg.sliding_window)
    mask = causal_mask(q.shape[1], cfg.sliding_window)
    return _grouped_attention(q, k, v, mask)


def attention_apply(params, cfg: ArchConfig, x: jax.Array,
                    positions: jax.Array,
                    cache: Optional[Dict[str, jax.Array]] = None,
                    cache_pos: Optional[jax.Array] = None,
                    impl: str = "chunked", flags=None,
                    block_tables: Optional[jax.Array] = None,
                    ) -> Tuple[jax.Array, Optional[Dict[str, jax.Array]]]:
    """Full-sequence (cache=None) or single-token decode (cache given).

    positions: [B, S] absolute positions.
    cache_pos: [] scalar — number of tokens already in the cache — or a
        [B] vector of per-row positions (continuous batching: each slot of
        the decode batch is an independent request at its own offset).
    block_tables: [B, P] int32 — paged decode: ``cache`` is a block-pool
        arena (``abstract_paged_kv_cache`` layout) and each row's K/V is
        reached through its block table instead of a contiguous row.
    """
    fused = cache is not None and paging.use_fused_decode(cfg, flags)
    q, k, v = _qkv(params, cfg, x, positions, rope=not fused)
    if cache is None:
        out = _seq_attention(q, k, v, cfg, impl, flags)
        y = jnp.einsum("bshk,hkd->bsd", out, params["wo"])
        return y, None

    if block_tables is not None:
        return _paged_decode(params, cfg, q, k, v, cache, cache_pos,
                             block_tables, flags, fused=fused)

    if fused:
        return _fused_slot_decode(params, cfg, q, k, v, cache, cache_pos,
                                  flags)

    # ---- decode: append S' token(s), attend to cache ------------------
    B, S, KV, hd = cache["k"].shape
    S_q = x.shape[1]
    window = cfg.sliding_window or 0
    cache_pos = jnp.asarray(cache_pos, jnp.int32)
    per_row = cache_pos.ndim == 1
    if S_q > 1:
        # Multi-token (speculative verify) decode: scatter all S' new
        # K/V at positions pos..pos+S'-1 of each row and give query s the
        # causal mask `idx <= pos + s`.  Row- and query-independence keep
        # every query's math identical to S' successive one-token decode
        # steps, which is the speculative bit-identity argument
        # (docs/SPECULATIVE.md).  Writes beyond a row's accepted prefix
        # are rolled back by the scheduler (positions rewind; stale
        # entries stay masked until overwritten by the next window).
        if window:
            raise ValueError("multi-token (speculative) decode does not "
                             "support sliding-window attention")
        if not per_row:
            raise ValueError("multi-token decode needs per-row cache_pos")
        slots = cache_pos[:, None] + jnp.arange(S_q)[None, :]   # [B,S']
        rows = jnp.arange(B)[:, None]
        k_new = cache["k"].at[rows, slots].set(k.astype(cache["k"].dtype))
        v_new = cache["v"].at[rows, slots].set(v.astype(cache["v"].dtype))
        valid = jnp.arange(S)[None, None, :] <= slots[:, :, None]
        mask = valid[:, None, None]                       # [B,1,1,S',T]
        out = _grouped_attention(q, k_new, v_new, mask)
        y = jnp.einsum("bshk,hkd->bsd", out, params["wo"])
        return y, {"k": k_new, "v": v_new}
    slot = (cache_pos % S) if window else cache_pos
    if per_row:
        rows = jnp.arange(B)
        k_new = cache["k"].at[rows, slot].set(
            k[:, 0].astype(cache["k"].dtype))
        v_new = cache["v"].at[rows, slot].set(
            v[:, 0].astype(cache["v"].dtype))
    else:
        k_new = cache["k"].at[:, slot].set(k[:, 0].astype(cache["k"].dtype))
        v_new = cache["v"].at[:, slot].set(v[:, 0].astype(cache["v"].dtype))
    idx = jnp.arange(S)
    pos = cache_pos[:, None] if per_row else cache_pos   # [B,1] or []
    if window:
        # with wraparound, every slot below min(cache_pos+1, S) is valid
        valid = idx < jnp.minimum(pos + 1, S)
    else:
        valid = idx <= pos                       # [B,T] or [T]
    mask = valid[:, None, None, None, :] if per_row \
        else valid[None, None, None, None, :]    # [B|1,1,1,1,T]
    mp = getattr(flags, "model_size", 1) if flags is not None else 1
    # per_row decode takes the generic path: the hd-sharded psum body
    # assumes one shared [T] validity mask, and a [B,T] mask needs per-row
    # plumbing through the shard_map before slot decode can use it on
    # meshes where KV heads don't divide the model axis
    if (mp > 1 and KV % mp != 0 and hd % mp == 0 and not per_row):
        # hd-sharded cache (kv heads don't divide the mesh): explicit
        # partial-score psum instead of XLA's full-cache all-gather
        # (EXPERIMENTS.md §Perf, jamba decode pair iteration 2).
        out = _decode_attention_hd_sharded(q, k_new, v_new, valid, flags)
    else:
        out = _grouped_attention(q, k_new, v_new, mask)
    y = jnp.einsum("bshk,hkd->bsd", out, params["wo"])
    return y, {"k": k_new, "v": v_new}


def prefill_into_cache(params, cfg: ArchConfig, x: jax.Array,
                       positions: jax.Array, max_len: int,
                       impl: str = "chunked", flags=None):
    """Run full attention over the prompt AND build the decode cache."""
    q, k, v = _qkv(params, cfg, x, positions)
    out = _seq_attention(q, k, v, cfg, impl, flags)
    y = jnp.einsum("bshk,hkd->bsd", out, params["wo"])
    window = cfg.sliding_window or 0
    S = x.shape[1]
    size = min(max_len, window) if window else max_len
    if window and S >= size:
        # keep the last `size` positions, rotated so slot = pos % size
        tail_k, tail_v = k[:, S - size:], v[:, S - size:]
        start = (S - size) % size
        k_c = jnp.roll(tail_k, start, axis=1)
        v_c = jnp.roll(tail_v, start, axis=1)
    else:
        pad = size - S
        k_c = jnp.pad(k, ((0, 0), (0, pad), (0, 0), (0, 0)))
        v_c = jnp.pad(v, ((0, 0), (0, pad), (0, 0), (0, 0)))
    return y, {"k": k_c, "v": v_c}


def _fused_decode_call(cfg: ArchConfig, flags, q, k, v, k_arena, v_arena,
                       tables, pos):
    """Dispatch one fused flash-decode call, single-device or
    tensor-parallel.

    Under a serving mesh (``flags.decode_mesh``, docs/SHARDING.md) the
    kernel is shard_mapped over the model axis: every rank runs the
    SAME kernel on its slice of the query/KV heads against its slice of
    the arena, with block tables and positions replicated.  Attention
    is head-parallel, so there is no cross-rank reduction at all — and
    because ``use_fused_decode`` only fuses when kv heads divide the
    mesh, each rank's GQA groups are self-contained.  Per-rank math is
    the single-device kernel's math on a head subset, so tokens stay
    bit-identical to the unsharded run."""
    from ..kernels.ops import fused_flash_decode
    split_k = getattr(flags, "fused_split_k", False) \
        if flags is not None else False
    mesh = getattr(flags, "decode_mesh", None) if flags is not None else None
    shards = getattr(flags, "decode_shards", 1) if flags is not None else 1
    if mesh is None or shards <= 1:
        return fused_flash_decode(q, k, v, k_arena, v_arena, tables, pos,
                                  rope_theta=cfg.rope_theta, split_k=split_k)
    from jax.sharding import PartitionSpec as P
    axis = getattr(flags, "model_axis", "model")
    hspec = P(None, None, axis, None)     # heads / kv_heads on dim 2

    def body(q_l, k_l, v_l, ka_l, va_l, tbl_l, pos_l):
        return fused_flash_decode(q_l, k_l, v_l, ka_l, va_l, tbl_l, pos_l,
                                  rope_theta=cfg.rope_theta, split_k=split_k)

    # outputs are genuinely sharded, never replicated: no vma check
    return jax.shard_map(
        body, mesh=mesh,
        in_specs=(hspec, hspec, hspec, hspec, hspec,
                  P(None, None), P(None)),
        out_specs=(hspec, hspec, hspec), check_vma=False)(
            q, k, v, k_arena, v_arena, tables, pos)


def _fused_slot_decode(params, cfg: ArchConfig, q, k, v, cache, cache_pos,
                       flags):
    """Contiguous-slot decode through the fused flash-decode kernel.

    The ``[B, max_len, KV, hd]`` cache is viewed (a free reshape) as a
    position-ordered arena of ``max_len // page`` blocks per row with
    identity-ish tables, so the SAME kernel serves the slot and paged
    layouts — and with matching page granularity
    (``paging.fused_page_size``) even the split-K accumulation order
    matches the paged backend's, keeping tokens bit-identical across
    layouts.  q/k/v arrive un-rotated (``_qkv(rope=False)``); the kernel
    rotates, scatters the window into the row, and attends with the
    per-query causal mask in one call.
    """
    B, S, KV, hd = cache["k"].shape
    pos = jnp.asarray(cache_pos, jnp.int32)
    if pos.ndim == 0:
        pos = jnp.broadcast_to(pos, (B,))
    page = paging.fused_page_size(S)
    P = S // page
    tables = paging.slot_arena_tables(B, S, page)
    k_arena = cache["k"].reshape(B * P, page, KV, hd)
    v_arena = cache["v"].reshape(B * P, page, KV, hd)
    out, k_arena, v_arena = _fused_decode_call(
        cfg, flags, q, k, v, k_arena, v_arena, tables, pos)
    y = jnp.einsum("bshk,hkd->bsd", out, params["wo"])
    return y, {"k": k_arena.reshape(B, S, KV, hd),
               "v": v_arena.reshape(B, S, KV, hd)}


def _paged_decode(params, cfg: ArchConfig, q, k, v, cache, cache_pos,
                  block_tables, flags, fused: bool = False):
    """Decode one (or, speculatively, S') token(s) against a paged arena.

    Each new token's K/V is scattered into the sequence's current tail
    block (``table[b, pos // bs]`` at offset ``pos % bs``); rows whose
    table entry is the trash block 0 (inactive slots, padding) write
    harmlessly there.  A single-token step then attends through
    :func:`_paged_attention` (the Pallas paged-attention kernel, which
    reads each row's live pages in place); a speculative window, and a
    layer whose kv heads do not divide the decode shards, gathers pages
    back into position order — which reconstructs exactly the
    contiguous cache row, keeping greedy decode bit-identical to the
    ``cache_pos`` path.
    """
    NB, bs, KV, hd = cache["k"].shape
    P = block_tables.shape[1]
    S_q = q.shape[1]
    pos = jnp.asarray(cache_pos, jnp.int32)          # [B] per-row positions
    if fused:
        # One pallas_call for the whole (possibly multi-token) window:
        # q/k/v arrive un-rotated; the kernel rotates at pos..pos+S'-1,
        # scatters k/v into each row's tail block(s) through its aliased
        # arena outputs, and attends query s with `idx <= pos + s`.
        out, k_new, v_new = _fused_decode_call(
            cfg, flags, q, k, v, cache["k"], cache["v"], block_tables, pos)
        y = jnp.einsum("bshk,hkd->bsd", out, params["wo"])
        return y, {"k": k_new, "v": v_new}
    if S_q > 1:
        # Multi-token (speculative verify) decode: scatter each of the S'
        # new tokens into its row's tail block at pos+s; query s is
        # masked to `idx <= pos + s` over the page-gathered sequence.
        # Window positions whose page is not in the table resolve to the
        # trash block 0 — the write is harmless and the positions stay
        # masked (the scheduler backs every position it will keep).
        pos_s = pos[:, None] + jnp.arange(S_q)[None, :]         # [B,S']
        with jax.named_scope("kv_write"):
            blk, off = paging.tail_refs(block_tables, pos_s, bs)
            k_new = paging.scatter_token(cache["k"], blk, off, k)
            v_new = paging.scatter_token(cache["v"], blk, off, v)
        with jax.named_scope("gather"):
            k_seq = paging.gather_pages(k_new, block_tables)
            v_seq = paging.gather_pages(v_new, block_tables)
        valid = jnp.arange(P * bs)[None, None, :] <= pos_s[:, :, None]
        mask = valid[:, None, None]                       # [B,1,1,S',T]
        out = _grouped_attention(q, k_seq, v_seq, mask)
        y = jnp.einsum("bshk,hkd->bsd", out, params["wo"])
        return y, {"k": k_new, "v": v_new}
    # named scopes under the layer's `attn`: the page gather and the K/V
    # write carry `attn/gather` and `attn/kv_write` in their op paths
    with jax.named_scope("kv_write"):
        blk, off = paging.tail_refs(block_tables, pos, bs)
        k_new = paging.scatter_token(cache["k"], blk, off, k[:, 0])
        v_new = paging.scatter_token(cache["v"], blk, off, v[:, 0])
    if paging.use_paged_attention(cfg, flags):
        out = _paged_attention(flags, q[:, 0], k_new, v_new, block_tables,
                               pos)[:, None]
    else:
        out = _gathered_attention(q, k_new, v_new, block_tables, pos)
    y = jnp.einsum("bshk,hkd->bsd", out, params["wo"])
    return y, {"k": k_new, "v": v_new}


def _gathered_attention(q, k_arena, v_arena, block_tables, pos):
    """Single-token attention over each row's page-gathered sequence
    (``[B, P*bs, KV, hd]``, masked past ``pos``): the contiguous slot
    path's math on the same values, bit for bit."""
    bs = k_arena.shape[1]
    with jax.named_scope("gather"):
        k_seq = paging.gather_pages(k_arena, block_tables)
        v_seq = paging.gather_pages(v_arena, block_tables)
    valid = paging.valid_mask(block_tables.shape[1] * bs, pos)
    return _grouped_attention(q, k_seq, v_seq,
                              valid[:, None, None, None, :])  # [B,1,1,1,T]


def _paged_attention_cpu(q, k_arena, v_arena, block_tables, pos):
    """:func:`_paged_attention` as lowered for the CPU: the page gather
    in XLA.  It is the slot path's math bit for bit, which the CPU tests
    pin paged serving to, and it spares every CPU serving test an
    interpreted kernel; ``tests/test_paged_attention.py`` swaps the
    interpreted kernel in for a served run."""
    return _gathered_attention(q[:, None], k_arena, v_arena, block_tables,
                               pos)[:, 0]


def _paged_kernel_call(flags, q, k_arena, v_arena, block_tables, pos):
    """The paged-attention kernel, single-device or tensor-parallel:
    under a serving mesh it is shard_mapped over the model axis with
    each rank's slice of the query and kv heads (the pattern of
    :func:`_fused_decode_call`; ``paging.use_paged_attention`` admits
    only kv heads that divide the mesh, so GQA groups stay rank-local),
    block tables and positions replicated."""
    from ..kernels.ops import paged_attention
    mesh = getattr(flags, "decode_mesh", None) if flags is not None else None
    shards = getattr(flags, "decode_shards", 1) if flags is not None else 1
    if mesh is None or shards <= 1:
        return paged_attention(q, k_arena, v_arena, block_tables, pos)
    from jax.sharding import PartitionSpec as P
    axis = getattr(flags, "model_axis", "model")
    return jax.shard_map(
        paged_attention, mesh=mesh,
        in_specs=(P(None, axis, None), P(None, None, axis, None),
                  P(None, None, axis, None), P(None, None), P(None)),
        out_specs=P(None, axis, None), check_vma=False)(
            q, k_arena, v_arena, block_tables, pos)


def _paged_attention(flags, q, k_arena, v_arena, block_tables, pos):
    """Single-token decode attention over a paged arena.  q: [B, H, hd];
    returns [B, H, hd].  On a TPU it is the Pallas paged-attention
    kernel (``kernels/paged_attention.py``), which reads only each
    row's live pages, in place; lowered for the CPU it is
    :func:`_paged_attention_cpu`."""
    return jax.lax.platform_dependent(
        q, k_arena, v_arena, block_tables, pos,
        cpu=_paged_attention_cpu,
        default=partial(_paged_kernel_call, flags))


def prefill_extend_into_cache(params, cfg: ArchConfig, x: jax.Array,
                              positions: jax.Array, prefix_kv: Dict,
                              prefix_len: int, max_len: int,
                              impl: str = "chunked", flags=None):
    """Prefill only the prompt *suffix*, attending over cached prefix K/V.

    x: [B, S'] suffix hidden states at global positions
    ``prefix_len .. prefix_len + S' - 1``; prefix_kv: k/v gathered from
    the paged arena for positions ``0 .. prefix_len - 1``.  Because each
    query row's attention is row-independent and the key sequence
    (prefix ++ suffix) is identical to the full-prompt prefill's, suffix
    activations — and therefore the first generated token — are
    bit-identical to a cold prefill of the whole prompt.
    """
    q, k, v = _qkv(params, cfg, x, positions)
    k_full = jnp.concatenate([prefix_kv["k"].astype(k.dtype), k], axis=1)
    v_full = jnp.concatenate([prefix_kv["v"].astype(v.dtype), v], axis=1)
    if impl == "chunked":
        out = chunked_attention_rect(q, k_full, v_full, prefix_len, cfg)
    elif impl == "flash":
        from ..kernels.ops import flash_attention
        out = flash_attention(q, k_full, v_full, causal=True,
                              window=cfg.sliding_window,
                              q_offset=prefix_len)
    elif impl == "naive":
        S_, T = q.shape[1], k_full.shape[1]
        i = prefix_len + jnp.arange(S_)[:, None]
        m = (jnp.arange(T)[None, :] <= i)[None, None, None]
        out = _grouped_attention(q, k_full, v_full, m)
    else:
        raise ValueError(f"prefix-extend prefill supports impl "
                         f"'chunked'|'naive'|'flash', got {impl!r}")
    y = jnp.einsum("bshk,hkd->bsd", out, params["wo"])
    S_in = x.shape[1]
    pad = max_len - S_in
    k_c = jnp.pad(k, ((0, 0), (0, pad), (0, 0), (0, 0)))
    v_c = jnp.pad(v, ((0, 0), (0, pad), (0, 0), (0, 0)))
    return y, {"k": k_c, "v": v_c}


def chunked_attention_rect(q, k, v, q_offset: int, cfg: ArchConfig):
    """Causal chunked attention for queries starting at ``q_offset``."""
    from .chunked_attention import chunked_attention
    return chunked_attention(q, k, v, causal=True,
                             window=cfg.sliding_window,
                             q_offset=jnp.asarray(q_offset, jnp.int32))


def _decode_attention_hd_sharded(q, k, v, valid, flags):
    """Decode attention with the head_dim sharded over the model axis:
    scores are contracted over the sharded hd (partial + psum of the SMALL
    [B,KV,G,1,T] score tensor); the value contraction stays local and the
    output remains hd-sharded for the (also hd-sharded) wo projection."""
    from jax.sharding import PartitionSpec as P
    B, S, H, hd = q.shape
    KV = k.shape[2]
    G = H // KV
    axis = flags.model_axis
    batch_axes = flags.batch_axes
    bspec = None
    if batch_axes and B % flags.batch_divisor == 0:
        bspec = batch_axes if len(batch_axes) > 1 else batch_axes[0]
    scale = 1.0 / jnp.sqrt(hd).astype(jnp.float32)

    def body(q_l, k_l, v_l, valid_l):
        qg = q_l.reshape(q_l.shape[0], 1, KV, G, -1)
        s = jnp.einsum("bskgd,btkd->bkgst", qg, k_l).astype(jnp.float32)
        s = jax.lax.psum(s, axis) * scale
        s = jnp.where(valid_l[None, None, None, None, :], s, NEG_INF)
        p = jax.nn.softmax(s, axis=-1).astype(q_l.dtype)
        o = jnp.einsum("bkgst,btkd->bskgd", p, v_l)
        return o.reshape(o.shape[0], 1, H, -1)

    return jax.shard_map(
        body,
        in_specs=(P(bspec, None, None, axis), P(bspec, None, None, axis),
                  P(bspec, None, None, axis), P(None)),
        out_specs=P(bspec, None, None, axis),
        check_vma=False,
    )(q, k, v, valid)
