"""Flash attention as a Pallas TPU kernel.

TPU adaptation of the blockwise online-softmax algorithm:

* grid = (batch, q_heads, num_q_blocks, num_kv_blocks); the LAST grid axis
  iterates innermost and sequentially on TPU, so the (m, l, acc) running
  statistics live in VMEM scratch carried across kv blocks;
* the kernel runs on a head-major ``[B, H, S, hd]`` view (the wrapper
  transposes), so every block is a ``(block, hd)`` tile whose last two
  dims meet Mosaic's (8, 128)-or-full-extent rule; a ``[B, S, H, hd]``
  block would put a 1 against ``H`` in the second-minor dim;
* GQA is expressed in the K/V index_map (query head h reads kv head
  h // group_size) — no repeated KV in HBM;
* causal/windowed masking is computed from block indices; fully-masked kv
  blocks write nothing and skip the matmuls via ``pl.when``.

Validated against ``ref.flash_attention_ref`` in interpret mode (CPU);
on a TPU the same code lowers through Mosaic (tests/test_tpu_compile.py
compiles it for a v5e chip).
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

NEG_INF = -1e30


def _flash_kernel(q_ref, k_ref, v_ref, o_ref, m_scr, l_scr, acc_scr, *,
                  causal: bool, window: int, block_q: int, block_k: int,
                  seq_q: int, seq_k: int, scale: float, q_offset: int):
    iq = pl.program_id(2)
    ik = pl.program_id(3)
    nk = pl.num_programs(3)

    @pl.when(ik == 0)
    def _init():
        m_scr[...] = jnp.full_like(m_scr, NEG_INF)
        l_scr[...] = jnp.zeros_like(l_scr)
        acc_scr[...] = jnp.zeros_like(acc_scr)

    q_start = q_offset + iq * block_q
    k_start = ik * block_k
    # block-level skip: no valid entries when the whole kv block is in the
    # causal future or behind the window
    run = True
    if causal:
        run = k_start <= q_start + block_q - 1
    if window:
        run = jnp.logical_and(run,
                              k_start + block_k - 1 > q_start - window)

    @pl.when(run)
    def _compute():
        q = q_ref[0, 0].astype(jnp.float32)              # [bq, hd]
        k = k_ref[0, 0].astype(jnp.float32)              # [bk, hd]
        v = v_ref[0, 0].astype(jnp.float32)              # [bk, hd]
        s = jax.lax.dot_general(q, k, (((1,), (1,)), ((), ())),
                                preferred_element_type=jnp.float32)
        s = s * scale                                     # [bq, bk]
        q_pos = q_start + jax.lax.broadcasted_iota(jnp.int32,
                                                   (block_q, block_k), 0)
        k_pos = k_start + jax.lax.broadcasted_iota(jnp.int32,
                                                   (block_q, block_k), 1)
        valid = k_pos < seq_k
        if causal:
            valid = valid & (k_pos <= q_pos)
        if window:
            valid = valid & (k_pos > q_pos - window)
        s = jnp.where(valid, s, NEG_INF)
        m_prev = m_scr[...]                               # [bq, 1]
        m_new = jnp.maximum(m_prev, s.max(axis=1, keepdims=True))
        p = jnp.exp(s - m_new)
        corr = jnp.exp(m_prev - m_new)
        l_scr[...] = l_scr[...] * corr + p.sum(axis=1, keepdims=True)
        acc_scr[...] = (acc_scr[...] * corr
                        + jax.lax.dot_general(
                            p, v, (((1,), (0,)), ((), ())),
                            preferred_element_type=jnp.float32))
        m_scr[...] = m_new

    @pl.when(ik == nk - 1)
    def _finalize():
        l = jnp.maximum(l_scr[...], 1e-30)
        o_ref[0, 0] = (acc_scr[...] / l).astype(o_ref.dtype)


def flash_attention_kernel(q: jax.Array, k: jax.Array, v: jax.Array, *,
                           causal: bool = True, window: int = 0,
                           q_offset: int = 0,
                           block_q: int = 128, block_k: int = 128,
                           interpret: bool = True) -> jax.Array:
    """q: [B,S,H,hd]; k,v: [B,T,KV,hd].  Returns [B,S,H,hd].

    ``q_offset`` places query row ``s`` at global position ``q_offset + s``
    for the causal/window masks — the rectangular suffix-attention shape
    of prefix-extend prefill (q covers positions ``q_offset ..
    q_offset + S - 1`` of a ``T``-long key sequence).

    The key sequence is padded up to a ``block_k`` multiple rather than
    shrinking ``block_k`` to fit, so the k-block partition boundaries are
    a fixed function of absolute position.  Padded/masked entries add
    exact f32 zeros to the online-softmax statistics, which makes each
    query row's accumulation order — and hence its output bits —
    independent of ``T``, ``q_offset``, and the q-block grouping.  That
    is the chunk-invariance argument for routing chunked prefill's
    suffix attention through this kernel (docs/KERNELS.md).  The query
    side is likewise padded up to a fixed ``block_q`` multiple, so every
    q block runs the same ``[block_q, block_k]`` matmul shape whatever
    ``S`` is."""
    B, S, H, hd = q.shape
    T, KV = k.shape[1], k.shape[2]
    G = H // KV
    pad_q = (-S) % block_q
    pad_k = (-T) % block_k
    # head-major view: [B, H, S, hd] blocks are (block, hd) tiles
    q = jnp.pad(q, ((0, 0), (0, pad_q), (0, 0), (0, 0))).transpose(0, 2, 1, 3)
    k = jnp.pad(k, ((0, 0), (0, pad_k), (0, 0), (0, 0))).transpose(0, 2, 1, 3)
    v = jnp.pad(v, ((0, 0), (0, pad_k), (0, 0), (0, 0))).transpose(0, 2, 1, 3)
    Sp, Tp = S + pad_q, T + pad_k
    nq, nk = Sp // block_q, Tp // block_k
    grid = (B, H, nq, nk)

    q_spec = pl.BlockSpec((1, 1, block_q, hd),
                          lambda b, h, iq, ik: (b, h, iq, 0))
    kv_spec = pl.BlockSpec((1, 1, block_k, hd),
                           lambda b, h, iq, ik: (b, h // G, ik, 0))

    kernel = functools.partial(
        _flash_kernel, causal=causal, window=window, block_q=block_q,
        block_k=block_k, seq_q=S, seq_k=T,
        scale=float(hd) ** -0.5, q_offset=q_offset)

    from jax.experimental.pallas import tpu as pltpu
    out = pl.pallas_call(
        kernel,
        grid=grid,
        in_specs=[q_spec, kv_spec, kv_spec],
        out_specs=q_spec,
        out_shape=jax.ShapeDtypeStruct((B, H, Sp, hd), q.dtype),
        scratch_shapes=[
            pltpu.VMEM((block_q, 1), jnp.float32),   # running max m
            pltpu.VMEM((block_q, 1), jnp.float32),   # normalizer l
            pltpu.VMEM((block_q, hd), jnp.float32),  # accumulator
        ],
        interpret=interpret,
    )(q, k, v)
    return out.transpose(0, 2, 1, 3)[:, :S]
