"""Paged decode attention as a Pallas TPU kernel.

Single-token decode attention where K/V live in a paged arena
(``[num_blocks, block_size, KV, hd]``) and each sequence's pages are
named by a block table, vLLM-style.  The kernel reads K/V *in place*
from the arena and only the pages that hold live positions:

* row ``b`` has ``positions[b] // block_size + 1`` live pages (capped
  at the table width).  Pages past them are never copied and never
  computed, so the work of a step follows the rows' live context, not
  the table width; an inactive row (position 0, all-zero table) costs
  one trash page;
* one grid step takes one *group* of up to ``pages_per_group`` live
  pages of one row, so the grid has ``sum_b ceil(live_b / group)``
  steps: a dynamic bound that never grows with the table width.  Each
  page of a group is its own block operand (the arena passed ``group``
  times), so the pipeline copies the next step's pages while this
  step's are computed.  A page slot a group does not fill keeps the
  block it held at the step before, which the pipeline does not copy
  again;
* the softmax is the split-K (online) recurrence in float32 — running
  max ``m``, sum ``l`` and accumulator per head, in VMEM scratch across
  a row's steps — one page at a time; only the row's last page holds
  positions past ``positions[b]``, so only it is masked (keys *and*
  values: a page slot past the data may hold anything, NaN included).

The pages are block operands, and not copied by hand from an arena
left in HBM, because a v5e tiles the arena's last two dims to (8, 128):
at MiniCPM-2B's 36 x 64 heads a page is not a whole number of tiles,
and Mosaic refuses a slice of such a ref.  The block pipeline copies
the tile-padded page (40 x 128 per position at 36 x 64: 2.2x the bytes
the page holds).

Scores are contracted on the vector unit (an elementwise product and a
reduction over head_dim), in float32 from the arena's dtype; decode
attention reads every K/V byte once, so it is bound by HBM bandwidth,
not by the matrix unit.  The kernel writes nothing but its output; the
new token's K/V is scattered into the arena before it runs.

Result: equal to ``repro.kernels.ref.paged_attention_ref`` (one
fully-gathered softmax) up to float32 reduction order.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

NEG_INF = -1e30

#: VMEM for the pages of one group of K (as much again for V), one
#: pipeline buffer: the group is this over the bytes of one padded page
GROUP_VMEM_BYTES = 3 << 19
#: most pages in one group (each is a block operand of its own)
MAX_GROUP = 8
#: Mosaic's default scoped-VMEM limit on a v5e, which the kernel must fit
DEFAULT_VMEM_LIMIT = 16 << 20


def padded_page_bytes(block_size: int, kv_heads: int, head_dim: int,
                      itemsize: int) -> int:
    """Bytes of one ``[block_size, KV, hd]`` page in VMEM, its last two
    dims padded to the (sublane, 128-lane) tile of its dtype."""
    sublanes = 8 * 4 // itemsize
    return (block_size * -(-kv_heads // sublanes) * sublanes
            * -(-head_dim // 128) * 128 * itemsize)


def pages_per_group(block_size: int, kv_heads: int, head_dim: int,
                    itemsize: int, table_width: int) -> int:
    """Pages per grid step: as many padded pages as fit
    ``GROUP_VMEM_BYTES``, at least 1, at most ``MAX_GROUP`` and the
    table width."""
    page = padded_page_bytes(block_size, kv_heads, head_dim, itemsize)
    return max(1, min(table_width, MAX_GROUP, GROUP_VMEM_BYTES // page))


def paged_vmem_bytes(block_size: int, kv_heads: int, head_dim: int,
                     itemsize: int, table_width: int) -> int:
    """VMEM the kernel's page buffers take: K and V, a group each, two
    pipeline buffers."""
    group = pages_per_group(block_size, kv_heads, head_dim, itemsize,
                            table_width)
    return 4 * group * padded_page_bytes(block_size, kv_heads, head_dim,
                                         itemsize)


def _schedule(block_tables: jax.Array, positions: jax.Array,
              block_size: int, group: int):
    """The grid's steps, row-major: (number of steps, row of each step,
    group of each step, flattened ``[step, slot]`` page ids).  Arrays
    are sized for the most steps the tables allow; the grid stops at
    the number of steps.  A slot past its row's live pages repeats the
    page it held at the step before (the first step's, the row's first
    page), so the pipeline copies nothing for it."""
    B, P = block_tables.shape
    live = jnp.minimum(positions // block_size + 1, P)           # [B]
    groups = -(-live // group)
    ends = jnp.cumsum(groups)
    steps = ends[-1]
    most = B * -(-P // group)
    s = jnp.arange(most, dtype=jnp.int32)
    row = jnp.minimum((ends[None, :] <= s[:, None]).sum(axis=1), B - 1)
    grp = s - (ends - groups)[row]
    j = grp[:, None] * group + jnp.arange(group)[None, :]        # [S, g]
    held = (j < live[row][:, None]) & (s < steps)[:, None]
    page = block_tables[row[:, None], jnp.minimum(j, P - 1)]
    last = jax.lax.cummax(jnp.where(held, s[:, None], -1), axis=0)
    pages = jnp.where(last >= 0,
                      page[jnp.maximum(last, 0), jnp.arange(group)[None, :]],
                      page[0, 0])
    return (steps.astype(jnp.int32), row.astype(jnp.int32),
            grp.astype(jnp.int32), pages.reshape(-1).astype(jnp.int32))


def _paged_kernel(pos_ref, row_ref, grp_ref, pages_ref, q_ref, *refs,
                  block_size: int, group: int, table_width: int):
    k_refs, v_refs = refs[:group], refs[group:2 * group]
    o_ref, m_ref, l_ref, acc_ref = refs[2 * group:]
    s = pl.program_id(0)
    pos = pos_ref[row_ref[s]]
    grp = grp_ref[s]
    n = jnp.minimum(pos // block_size + 1, table_width)
    G, KV, hd = q_ref.shape[1], q_ref.shape[2], q_ref.shape[3]
    f32 = jnp.float32

    @pl.when(grp == 0)
    def _init():
        m_ref[...] = jnp.full(m_ref.shape, NEG_INF, f32)
        l_ref[...] = jnp.zeros(l_ref.shape, f32)
        acc_ref[...] = jnp.zeros(acc_ref.shape, f32)

    scale = 1.0 / jnp.sqrt(jnp.asarray(hd, f32))

    def visit(i: int, masked: bool):
        j = grp * group + i
        k = k_refs[i][0].astype(f32)                     # [bs, KV, hd]
        v = v_refs[i][0].astype(f32)
        if masked:
            idx = j * block_size + jax.lax.broadcasted_iota(
                jnp.int32, (block_size, KV, 1), 0)
            valid = idx <= pos
            v = jnp.where(valid, v, 0.0)
        for g in range(G):
            q = q_ref[0, g].astype(f32) * scale          # [KV, hd]
            sc = jnp.sum(k * q[None], axis=-1, keepdims=True)   # [bs, KV, 1]
            if masked:
                sc = jnp.where(valid, sc, NEG_INF)
            m_prev = m_ref[g]                            # [KV, 1]
            m_new = jnp.maximum(m_prev, jnp.max(sc, axis=0))
            p = jnp.exp(sc - m_new[None])
            corr = jnp.exp(m_prev - m_new)
            l_ref[g] = l_ref[g] * corr + jnp.sum(p, axis=0)
            acc_ref[g] = acc_ref[g] * corr + jnp.sum(p * v, axis=0)
            m_ref[g] = m_new

    for i in range(group):
        j = grp * group + i
        pl.when(j < n - 1)(functools.partial(visit, i, False))
        pl.when(j == n - 1)(functools.partial(visit, i, True))

    @pl.when((grp + 1) * group >= n)
    def _finish():
        o_ref[0] = (acc_ref[...] / l_ref[...]).astype(o_ref.dtype)


def paged_attention_kernel(q: jax.Array, k_pages: jax.Array,
                           v_pages: jax.Array, block_tables: jax.Array,
                           positions: jax.Array, *,
                           interpret: bool = True) -> jax.Array:
    """q: [B, H, hd]; k_pages/v_pages: [NB, bs, KV, hd];
    block_tables: [B, P] int32; positions: [B] int32 (keys at index <=
    ``positions[b]`` are attended).  Returns [B, H, hd].
    """
    B, H, hd = q.shape
    bs, KV = k_pages.shape[1], k_pages.shape[2]
    P = block_tables.shape[1]
    G = H // KV
    group = pages_per_group(bs, KV, hd, k_pages.dtype.itemsize, P)
    positions = jnp.asarray(positions, jnp.int32)
    steps, row, grp, pages = _schedule(
        jnp.asarray(block_tables, jnp.int32), positions, bs, group)
    # head h = kv * G + g: the kernel takes the query as [B, G, KV, hd]
    qg = q.reshape(B, KV, G, hd).transpose(0, 2, 1, 3)
    row_spec = pl.BlockSpec((1, G, KV, hd),
                            lambda s, pos, row, grp, pg: (row[s], 0, 0, 0))

    def page_spec(i):
        return pl.BlockSpec(
            (1, bs, KV, hd),
            lambda s, pos, row, grp, pg: (pg[s * group + i], 0, 0, 0))

    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=4,          # positions, row, group, pages
        grid=(steps,),
        in_specs=[row_spec] + [page_spec(i) for i in range(group)] * 2,
        out_specs=row_spec,
        scratch_shapes=[pltpu.VMEM((G, KV, 1), jnp.float32),
                        pltpu.VMEM((G, KV, 1), jnp.float32),
                        pltpu.VMEM((G, KV, hd), jnp.float32)],
    )
    kernel = functools.partial(_paged_kernel, block_size=bs, group=group,
                               table_width=P)
    out = pl.pallas_call(
        kernel,
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((B, G, KV, hd), q.dtype),
        # a row's steps run in order and carry m/l/acc in scratch
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary",)),
        interpret=interpret,
    )(positions, row, grp, pages, qg, *([k_pages] * group),
      *([v_pages] * group))
    return out.transpose(0, 2, 1, 3).reshape(B, H, hd)
