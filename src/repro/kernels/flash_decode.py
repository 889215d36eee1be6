"""Fused flash-decode attention as a Pallas TPU kernel family.

One ``pallas_call`` per decode (or speculative-verify) step that
collapses the pure-JAX ``gather → RoPE → scatter → dot → softmax`` chain
the serving hot path used to issue as separate XLA ops:

* **query windows** — queries are ``[B, S', H, hd]`` with ``S' = 1 + k``
  (plain decode is the ``S' = 1`` special case); query ``s`` gets the
  per-query causal mask ``idx <= pos + s``, which is what lets a whole
  speculative verify window run in-kernel instead of falling back to the
  page-gather path;
* **RoPE fusion** — q and the new K tokens arrive *un-rotated*; the
  kernel applies rotary embedding at positions ``pos .. pos + S' - 1``
  with bit-for-bit the same f32 expression as
  ``repro.models.layers.apply_rope``, so the cache contents it writes are
  indistinguishable from the unfused path's;
* **scatter fusion** — the rotated new K (and V) tokens are written into
  the paged arena *through the kernel's aliased outputs*
  (``input_output_aliases``): each grid step stages its page, overlays
  any window token that lands in it, and writes the page back to its own
  block.  The write-back is idempotent for untouched pages, so no trash
  redirect is needed and the same kernel serves a contiguous slot cache
  viewed as a one-row-per-sequence arena (see
  ``repro.models.paging.slot_arena_tables``);
* **split-K** — the default variant stages the whole row into VMEM
  scratch and runs one fully-gathered softmax (bit-exact against
  ``repro.kernels.ref.fused_flash_decode_ref``).  ``split_k=True``
  switches to an
  online-softmax recurrence with per-page partial reductions (m/l/acc
  scratch) that *skips the attention math for pages past the last valid
  position* — work becomes proportional to the row's actual length
  instead of the table width.  Masked entries contribute exactly +0.0
  (``exp(NEG_INF - m)`` underflows to zero in f32), so split-K agrees
  with the gathered variant to f32 reduction-order tolerance; the
  gathered variant stays the bit-exact reference configuration.

Contract (shared with the ref oracle):

* ``block_tables`` are position-ordered; page ``p`` of row ``b`` holds
  global positions ``[p*bs, (p+1)*bs)``.  Padding entries are the trash
  block 0 and may only *trail* the row's valid pages.
* The caller guarantees ``positions[b] + S' <= P * bs`` for rows whose
  output it consumes.  Rows whose *window* pages resolve to the trash
  block (inactive slots) produce finite but unspecified attention
  output, and block 0's content is unspecified after the call — exactly
  the conventions the paged allocator already lives by.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

NEG_INF = -1e30

#: Scoped-VMEM cap for the fully-gathered variant.  It stages the whole
#: row (two ``[T, KV, hd]`` scratches, lane-padded, plus their f32 views)
#: in VMEM: at MiniCPM-2B widths with T=2048 that is over 100 MiB, far
#: past Mosaic's 16 MiB default.  A v5e core has 128 MiB of VMEM; rows
#: that do not fit under this cap belong to the split-K variant.
GATHER_VMEM_LIMIT = 124 << 20
#: Mosaic's VMEM need of the gathered variant per byte of one tile-padded
#: ``[T, KV, hd]`` row: the two row scratches, their f32 working copies
#: and the register spill they compile to.  Compiled for a described v5e
#: at 36 KV heads x 64 it needed 107 MiB at T=2048 and 213 MiB at
#: T=4096: 5.3 row bytes each time.
GATHER_VMEM_PER_ROW_BYTE = 5.33


def gathered_vmem_bytes(rows: int, kv_heads: int, head_dim: int,
                        itemsize: int = 2) -> int:
    """Estimated scoped VMEM the gathered variant needs for ``rows``
    cached positions of ``kv_heads`` x ``head_dim`` (one shard's heads
    under tensor parallelism); compare with ``GATHER_VMEM_LIMIT``."""
    row = -(-kv_heads // 8) * 8 * (-(-head_dim // 128) * 128) * itemsize
    return int(GATHER_VMEM_PER_ROW_BYTE * rows * row)


@functools.partial(jax.jit, static_argnums=(0, 1))
def rope_freqs(hd: int, theta: float) -> jax.Array:
    """[1, hd/2] inverse rotary frequencies, computed OUTSIDE the kernel
    body and passed in as an operand.

    The expression is ``models.layers.rope_frequencies`` verbatim, and it
    must stay under jit: XLA constant-folds ``arange(0, hd, 2) / hd``
    differently from an in-kernel ``iota * 2.0 / hd`` (and from its own
    eager value) whenever ``hd`` is not a power of two — div-by-constant
    is rewritten form-dependently, a 1-ulp spread that breaks the
    kernel == jit(oracle) bit-exactness contract.  Powers of two are
    immune (exact division), which is why the divergence only shows up
    for head dims like 48 or 96.
    """
    return (1.0 / (theta ** (jnp.arange(0, hd, 2, dtype=jnp.float32)
                             / hd)))[None, :]


def _rope_window(x: jax.Array, pos: jax.Array,
                 freqs: jax.Array) -> jax.Array:
    """Rotary embedding for a decode window.

    x: [S', heads, hd] float32; pos: scalar int32 — token s sits at
    absolute position pos + s; freqs: [1, hd/2] from ``rope_freqs``.
    Mirrors ``models.layers.apply_rope`` expression-for-expression (same
    f32 ops in the same order) so the fused path is bitwise
    indistinguishable from rotating outside the kernel.
    """
    Sq, _, hd = x.shape
    positions = pos + jax.lax.broadcasted_iota(jnp.int32, (Sq, 1), 0)
    angles = positions.astype(jnp.float32) * freqs             # [S', hd/2]
    cos = jnp.cos(angles)[:, None, :]                          # [S', 1, hd/2]
    sin = jnp.sin(angles)[:, None, :]
    x1, x2 = jnp.split(x, 2, axis=-1)
    return jnp.concatenate([x1 * cos - x2 * sin,
                            x2 * cos + x1 * sin], axis=-1)


def _stage_page(k_ref, v_ref, kn_ref, vn_ref, freqs, k_dst, v_dst, *,
                p, pos, block_size, dst_offset):
    """Copy arena page p into scratch and overlay window tokens.

    ``dst_offset`` is the scratch index of the page's first position:
    ``p * block_size`` for the fully-gathered [T, ...] scratch, 0 for the
    per-page [bs, ...] split-K scratch.
    """
    Sq = kn_ref.shape[1]
    k_dst[pl.ds(dst_offset, block_size)] = k_ref[0]
    v_dst[pl.ds(dst_offset, block_size)] = v_ref[0]
    kn = _rope_window(kn_ref[0].astype(jnp.float32), pos,
                      freqs).astype(k_ref.dtype)
    vn = vn_ref[0].astype(v_ref.dtype)
    for s in range(Sq):
        g = pos + s

        @pl.when(g // block_size == p)
        def _overlay(s=s, g=g):
            k_dst[pl.ds(dst_offset + g % block_size, 1)] = kn[s:s + 1]
            v_dst[pl.ds(dst_offset + g % block_size, 1)] = vn[s:s + 1]


def _fused_gather_kernel(tables_ref, pos_ref, q_ref, kn_ref, vn_ref,
                         k_ref, v_ref, freqs_ref, o_ref, ko_ref, vo_ref,
                         k_scr, v_scr, *, block_size: int, kv_heads: int):
    b = pl.program_id(0)
    p = pl.program_id(1)
    num_pages = pl.num_programs(1)
    pos = pos_ref[b]
    freqs = freqs_ref[...]

    _stage_page(k_ref, v_ref, kn_ref, vn_ref, freqs, k_scr, v_scr,
                p=p, pos=pos, block_size=block_size,
                dst_offset=p * block_size)
    # write the (possibly overlaid) page back to its own block — the
    # aliased-output scatter; idempotent for pages outside the window
    ko_ref[0] = k_scr[pl.ds(p * block_size, block_size)]
    vo_ref[0] = v_scr[pl.ds(p * block_size, block_size)]

    @pl.when(p == num_pages - 1)
    def _attend():
        T = num_pages * block_size
        Sq, H, hd = q_ref.shape[1], q_ref.shape[2], q_ref.shape[3]
        G = H // kv_heads
        qf = _rope_window(q_ref[0].astype(jnp.float32), pos, freqs)
        qg = qf.reshape(Sq, kv_heads, G, hd)
        k = k_scr[...].astype(jnp.float32)                # [T, KV, hd]
        v = v_scr[...].astype(jnp.float32)
        # same contraction and scale expression as the ref oracle
        # (bit-exactness contract: float(hd)**-0.5 rounds from float64
        # and is 1 ulp off for non-power-of-two head dims)
        scale = 1.0 / jnp.sqrt(jnp.asarray(hd, jnp.float32))
        s = jax.lax.dot_general(
            qg, k, (((3,), (2,)), ((1,), (1,))),
            preferred_element_type=jnp.float32) * scale   # [KV, S', G, T]
        idx = jax.lax.broadcasted_iota(jnp.int32, (1, 1, 1, T), 3)
        qi = jax.lax.broadcasted_iota(jnp.int32, (1, Sq, 1, 1), 1)
        s = jnp.where(idx <= pos + qi, s, NEG_INF)
        m = s.max(axis=-1)
        prob = jnp.exp(s - m[..., None])
        l = prob.sum(axis=-1)
        o = jax.lax.dot_general(
            prob, v, (((3,), (0,)), ((0,), (1,))),
            preferred_element_type=jnp.float32)           # [KV, S', G, hd]
        o = o / l[..., None]
        o_ref[0] = o.transpose(1, 0, 2, 3).reshape(Sq, H, hd
                                                   ).astype(o_ref.dtype)


def _fused_splitk_kernel(tables_ref, pos_ref, q_ref, kn_ref, vn_ref,
                         k_ref, v_ref, freqs_ref, o_ref, ko_ref, vo_ref,
                         kp_scr, vp_scr, m_scr, l_scr, acc_scr, *,
                         block_size: int, kv_heads: int):
    b = pl.program_id(0)
    p = pl.program_id(1)
    Sq, H, hd = q_ref.shape[1], q_ref.shape[2], q_ref.shape[3]
    G = H // kv_heads
    pos = pos_ref[b]
    freqs = freqs_ref[...]
    # last page holding any valid key: everything past it is fully masked
    # for every query in the window, so its attention math is skipped
    last = (pos + Sq - 1) // block_size

    _stage_page(k_ref, v_ref, kn_ref, vn_ref, freqs, kp_scr, vp_scr,
                p=p, pos=pos, block_size=block_size, dst_offset=0)
    ko_ref[0] = kp_scr[...]
    vo_ref[0] = vp_scr[...]

    @pl.when(p == 0)
    def _init():
        m_scr[...] = jnp.full(m_scr.shape, NEG_INF, jnp.float32)
        l_scr[...] = jnp.zeros(l_scr.shape, jnp.float32)
        acc_scr[...] = jnp.zeros(acc_scr.shape, jnp.float32)

    @pl.when(p <= last)
    def _partial():
        qf = _rope_window(q_ref[0].astype(jnp.float32), pos, freqs)
        qg = qf.reshape(Sq, kv_heads, G, hd)
        k = kp_scr[...].astype(jnp.float32)               # [bs, KV, hd]
        v = vp_scr[...].astype(jnp.float32)
        scale = 1.0 / jnp.sqrt(jnp.asarray(hd, jnp.float32))
        s = jax.lax.dot_general(
            qg, k, (((3,), (2,)), ((1,), (1,))),
            preferred_element_type=jnp.float32) * scale   # [KV, S', G, bs]
        idx = p * block_size + jax.lax.broadcasted_iota(
            jnp.int32, (1, 1, 1, block_size), 3)
        qi = jax.lax.broadcasted_iota(jnp.int32, (1, Sq, 1, 1), 1)
        s = jnp.where(idx <= pos + qi, s, NEG_INF)
        # online-softmax update: masked entries contribute exactly +0.0
        # (exp underflow), so partial order only perturbs f32 rounding
        m_prev = m_scr[...]
        m_new = jnp.maximum(m_prev, s.max(axis=-1))
        prob = jnp.exp(s - m_new[..., None])
        corr = jnp.exp(m_prev - m_new)
        l_scr[...] = l_scr[...] * corr + prob.sum(axis=-1)
        pv = jax.lax.dot_general(
            prob, v, (((3,), (0,)), ((0,), (1,))),
            preferred_element_type=jnp.float32)
        acc_scr[...] = acc_scr[...] * corr[..., None] + pv
        m_scr[...] = m_new

        @pl.when(p == last)
        def _finalize():
            o = acc_scr[...] / l_scr[...][..., None]
            o_ref[0] = o.transpose(1, 0, 2, 3).reshape(Sq, H, hd
                                                       ).astype(o_ref.dtype)


def fused_flash_decode_kernel(q: jax.Array, k_new: jax.Array,
                              v_new: jax.Array, k_pages: jax.Array,
                              v_pages: jax.Array, block_tables: jax.Array,
                              positions: jax.Array, *,
                              rope_theta: float = 10_000.0,
                              split_k: bool = False,
                              interpret: bool = True):
    """Fused decode/verify attention over a paged arena.

    q: [B, S', H, hd] un-rotated queries (qk-norm, if any, already
        applied); k_new/v_new: [B, S', KV, hd] un-rotated new K / new V;
    k_pages/v_pages: [NB, bs, KV, hd] arena (updated in place through
        ``input_output_aliases``);
    block_tables: [B, P] int32; positions: [B] int32 window starts.

    Returns ``(out [B, S', H, hd], k_pages, v_pages)`` — the arenas with
    the rotated window scattered into each row's tail block(s).
    """
    B, Sq, H, hd = q.shape
    bs, KV = k_pages.shape[1], k_pages.shape[2]
    P = block_tables.shape[1]
    T = P * bs

    from jax.experimental.pallas import tpu as pltpu

    G = H // KV
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=2,          # block_tables, positions
        grid=(B, P),
        in_specs=[
            pl.BlockSpec((1, Sq, H, hd), lambda b, p, tbl, pos: (b, 0, 0, 0)),
            pl.BlockSpec((1, Sq, KV, hd),
                         lambda b, p, tbl, pos: (b, 0, 0, 0)),
            pl.BlockSpec((1, Sq, KV, hd),
                         lambda b, p, tbl, pos: (b, 0, 0, 0)),
            pl.BlockSpec((1, bs, KV, hd),
                         lambda b, p, tbl, pos: (tbl[b, p], 0, 0, 0)),
            pl.BlockSpec((1, bs, KV, hd),
                         lambda b, p, tbl, pos: (tbl[b, p], 0, 0, 0)),
            pl.BlockSpec((1, hd // 2), lambda b, p, tbl, pos: (0, 0)),
        ],
        out_specs=[
            pl.BlockSpec((1, Sq, H, hd), lambda b, p, tbl, pos: (b, 0, 0, 0)),
            pl.BlockSpec((1, bs, KV, hd),
                         lambda b, p, tbl, pos: (tbl[b, p], 0, 0, 0)),
            pl.BlockSpec((1, bs, KV, hd),
                         lambda b, p, tbl, pos: (tbl[b, p], 0, 0, 0)),
        ],
        scratch_shapes=(
            [pltpu.VMEM((bs, KV, hd), k_pages.dtype),
             pltpu.VMEM((bs, KV, hd), v_pages.dtype),
             pltpu.VMEM((KV, Sq, G), jnp.float32),
             pltpu.VMEM((KV, Sq, G), jnp.float32),
             pltpu.VMEM((KV, Sq, G, hd), jnp.float32)]
            if split_k else
            [pltpu.VMEM((T, KV, hd), k_pages.dtype),
             pltpu.VMEM((T, KV, hd), v_pages.dtype)]),
    )
    body = _fused_splitk_kernel if split_k else _fused_gather_kernel
    kernel = functools.partial(body, block_size=bs, kv_heads=KV)
    return pl.pallas_call(
        kernel,
        grid_spec=grid_spec,
        out_shape=[
            jax.ShapeDtypeStruct((B, Sq, H, hd), q.dtype),
            jax.ShapeDtypeStruct(k_pages.shape, k_pages.dtype),
            jax.ShapeDtypeStruct(v_pages.shape, v_pages.dtype),
        ],
        # operand indices count the scalar-prefetch args: tables(0),
        # positions(1), q(2), k_new(3), v_new(4), k_pages(5), v_pages(6),
        # freqs(7)
        input_output_aliases={5: 1, 6: 2},
        compiler_params=(None if split_k else pltpu.CompilerParams(
            vmem_limit_bytes=GATHER_VMEM_LIMIT)),
        interpret=interpret,
    )(jnp.asarray(block_tables, jnp.int32),
      jnp.asarray(positions, jnp.int32), q, k_new, v_new, k_pages, v_pages,
      rope_freqs(hd, rope_theta))
