"""Pure-jnp oracles for the Pallas kernels (the correctness ground truth
for per-kernel allclose tests)."""
from __future__ import annotations

import jax
import jax.numpy as jnp

NEG_INF = -1e30


def flash_attention_ref(q: jax.Array, k: jax.Array, v: jax.Array, *,
                        causal: bool = True, window: int = 0) -> jax.Array:
    """q: [B,S,H,hd]; k,v: [B,T,KV,hd]; GQA by head grouping."""
    B, S, H, hd = q.shape
    T, KV = k.shape[1], k.shape[2]
    G = H // KV
    qg = q.reshape(B, S, KV, G, hd).astype(jnp.float32)
    kf = k.astype(jnp.float32)
    vf = v.astype(jnp.float32)
    scores = jnp.einsum("bskgd,btkd->bkgst", qg, kf) / jnp.sqrt(hd)
    i = jnp.arange(S)[:, None]
    j = jnp.arange(T)[None, :]
    m = jnp.ones((S, T), bool)
    if causal:
        m = m & (j <= i)
    if window:
        m = m & (j > i - window)
    scores = jnp.where(m[None, None, None], scores, NEG_INF)
    probs = jax.nn.softmax(scores, axis=-1)
    out = jnp.einsum("bkgst,btkd->bskgd", probs, vf)
    return out.reshape(B, S, H, hd).astype(q.dtype)


def rmsnorm_ref(x: jax.Array, scale: jax.Array,
                eps: float = 1e-5) -> jax.Array:
    xf = x.astype(jnp.float32)
    var = jnp.mean(xf * xf, axis=-1, keepdims=True)
    return (xf * jax.lax.rsqrt(var + eps)
            * scale.astype(jnp.float32)).astype(x.dtype)


def fused_flash_decode_ref(q: jax.Array, k_new: jax.Array, v_new: jax.Array,
                           k_pages: jax.Array, v_pages: jax.Array,
                           block_tables: jax.Array, positions: jax.Array, *,
                           rope_theta: float = 10_000.0):
    """Fused decode/verify-window attention, pure JAX.

    The bit-exactness oracle for the fully-gathered
    ``fused_flash_decode_kernel`` (the split-K variant agrees to f32
    reduction-order tolerance).  Semantics per row ``b`` holding
    ``positions[b]`` tokens:

    1. rotate q and k_new at absolute positions ``pos .. pos + S' - 1``
       with the exact ``models.layers.apply_rope`` f32 expression;
    2. scatter the rotated k_new / v_new window into the row's tail
       block(s) (``block_tables[b, g // bs]`` at offset ``g % bs``);
    3. attend each query ``s`` over the updated pages gathered in
       position order, masked to ``idx <= pos + s``, with the same
       op sequence as ``paged_attention_ref``.

    q: [B, S', H, hd] un-rotated; k_new/v_new: [B, S', KV, hd]
    un-rotated; k_pages/v_pages: [NB, bs, KV, hd]; block_tables: [B, P]
    int32 (position-ordered, trailing 0-padding); positions: [B] int32.
    Caller guarantees ``positions[b] + S' <= P * bs`` for consumed rows;
    rows whose window pages resolve to the trash block 0 have
    unspecified output, and block 0 content is unspecified after the
    call (the kernel and the oracle clobber it differently).

    Returns ``(out [B, S', H, hd], k_pages', v_pages')``.
    """
    B, Sq, H, hd = q.shape
    bs, KV = k_pages.shape[1], k_pages.shape[2]
    P = block_tables.shape[1]
    G = H // KV
    T = P * bs
    scale = 1.0 / jnp.sqrt(jnp.asarray(hd, jnp.float32))

    # rotate — the same f32 expression as models.layers.apply_rope
    freqs = 1.0 / (rope_theta ** (jnp.arange(0, hd, 2,
                                             dtype=jnp.float32) / hd))
    pos_s = positions[:, None] + jnp.arange(Sq, dtype=jnp.int32)  # [B, S']
    angles = pos_s[..., None].astype(jnp.float32) * freqs    # [B, S', hd/2]
    cos = jnp.cos(angles)[..., None, :]                      # [B, S', 1, ...]
    sin = jnp.sin(angles)[..., None, :]

    def rot(x):
        x1, x2 = jnp.split(x.astype(jnp.float32), 2, axis=-1)
        return jnp.concatenate([x1 * cos - x2 * sin,
                                x2 * cos + x1 * sin], axis=-1)

    q_r = rot(q)                                     # [B, S', H, hd] f32
    k_r = rot(k_new).astype(k_pages.dtype)
    v_c = v_new.astype(v_pages.dtype)

    # scatter the window, rows in kernel grid order (b outer, s inner)
    for b in range(B):
        for s in range(Sq):
            g = positions[b] + s
            blk = block_tables[b, g // bs]
            k_pages = k_pages.at[blk, g % bs].set(k_r[b, s])
            v_pages = v_pages.at[blk, g % bs].set(v_c[b, s])

    def one(args):
        q_b, tbl, pos = args                             # q_b: [S', H, hd]
        k = k_pages[tbl].reshape(T, KV, hd).astype(jnp.float32)
        v = v_pages[tbl].reshape(T, KV, hd).astype(jnp.float32)
        qg = q_b.reshape(Sq, KV, G, hd)
        # [KV, S', G, T]: batch over KV heads, contract head_dim
        s = jax.lax.dot_general(
            qg, k, (((3,), (2,)), ((1,), (1,))),
            preferred_element_type=jnp.float32) * scale
        idx = jnp.arange(T, dtype=jnp.int32)[None, None, None, :]
        qi = jnp.arange(Sq, dtype=jnp.int32)[None, :, None, None]
        s = jnp.where(idx <= pos + qi, s, NEG_INF)
        m = s.max(axis=-1)
        p = jnp.exp(s - m[..., None])
        l = p.sum(axis=-1)
        o = jax.lax.dot_general(
            p, v, (((3,), (0,)), ((0,), (1,))),
            preferred_element_type=jnp.float32)
        return (o / l[..., None]).transpose(1, 0, 2, 3).reshape(Sq, H, hd)

    out = jax.lax.map(one, (q_r, block_tables, positions))
    return out.astype(q.dtype), k_pages, v_pages


def paged_attention_ref(q: jax.Array, k_pages: jax.Array, v_pages: jax.Array,
                        block_tables: jax.Array, positions: jax.Array
                        ) -> jax.Array:
    """Paged single-token decode attention, pure JAX.

    q: [B, H, hd] — one query per sequence (decode step).
    k_pages/v_pages: [num_blocks, block_size, KV, hd] — the paged arena.
    block_tables: [B, P] int32 — per-sequence page ids (0-padded; block 0
        is the trash block, always masked).
    positions: [B] int32 — current cache position; keys at index <= pos
        are attended (the position being written included).

    This is the oracle for the Pallas paged-attention kernel: one
    fully-gathered fp32 softmax per row, which the kernel's online
    softmax over live pages matches to fp32 reduction order
    (``tests/test_paged_attention.py``).
    """
    B, H, hd = q.shape
    bs, KV = k_pages.shape[1], k_pages.shape[2]
    P = block_tables.shape[1]
    G = H // KV
    T = P * bs
    scale = 1.0 / jnp.sqrt(jnp.asarray(hd, jnp.float32))

    def one(args):
        q_b, tbl, pos = args
        k = k_pages[tbl].reshape(T, KV, hd).astype(jnp.float32)
        v = v_pages[tbl].reshape(T, KV, hd).astype(jnp.float32)
        qg = q_b.reshape(KV, G, hd).astype(jnp.float32)
        # [KV, G, T]: batch over KV heads, contract head_dim
        s = jax.lax.dot_general(
            qg, k, (((2,), (2,)), ((0,), (1,))),
            preferred_element_type=jnp.float32) * scale
        valid = jnp.arange(T) <= pos
        s = jnp.where(valid[None, None, :], s, NEG_INF)
        m = s.max(axis=-1)
        p = jnp.exp(s - m[..., None])
        l = p.sum(axis=-1)
        o = jax.lax.dot_general(
            p, v, (((2,), (0,)), ((0,), (1,))),
            preferred_element_type=jnp.float32)
        return (o / l[..., None]).reshape(H, hd)

    out = jax.lax.map(one, (q, block_tables, positions))
    return out.astype(q.dtype)
