"""Model composition: per-layer blocks (attn / MLA / mamba / m-sLSTM ×
dense / MoE FFN), repeated-group layer stacking via ``lax.scan`` (compile
time stays flat in depth), encoder-decoder wiring, MTP head, and the three
entry points used by the runtime: ``forward`` (train), ``prefill`` and
``decode_step``.
"""
from __future__ import annotations

import dataclasses
from functools import partial
from typing import Any, Dict, List, Optional, Tuple

import jax
import jax.numpy as jnp

from . import attention as attn
from . import mamba as mam
from . import mla as mla_mod
from . import moe as moe_mod
from . import paging
from . import xlstm as xl
from .config import ArchConfig
from .layers import (embed_apply, embed_template, lm_head_apply,
                     lm_head_template, mlp_apply, mlp_template, rms_norm,
                     rmsnorm_template)
from .params import ParamSpec, Template, stack_template


@dataclasses.dataclass(frozen=True)
class RuntimeFlags:
    use_flash: bool = False          # Pallas flash-attention for seq paths
    attn_impl: str = "chunked"       # "chunked" | "naive" ("flash" wins if set)
    remat: str = "group"             # "none" | "group"
    fused_rmsnorm: bool = False
    # Explicit activation sharding: batch dim of [B, S, d] activations is
    # pinned to these mesh axes at every layer boundary (SPMD propagation
    # alone loses the sharding inside remat'd scans — see EXPERIMENTS §Perf).
    batch_axes: Tuple[str, ...] = ()
    batch_divisor: int = 1
    # MoE implementation: "gather" (pure jnp, any device count) or "ep"
    # (shard_map expert parallelism over the model axis)
    moe_impl: str = "gather"
    model_axis: str = "model"
    model_size: int = 1
    # Fused flash-decode: run the whole decode / speculative-verify
    # window (RoPE + tail-block KV scatter + per-query-masked attention)
    # as one Pallas call on every layout — slot rows are viewed as a
    # one-row-per-sequence arena (paging.slot_arena_tables).  MLA and
    # sliding-window layers fall back to the gather path per layer
    # (paging.use_fused_decode / runtime.steps.kernel_path).
    use_fused_decode: bool = False
    # Fused-decode variant: online-softmax partial reductions per page
    # that skip pages past the row's length (work ∝ actual context);
    # False = the fully-gathered bit-exact reference configuration.
    fused_split_k: bool = False
    # Tensor-parallel SERVING (docs/SHARDING.md): set by LLMEngine when
    # it is built with a device mesh.  ``decode_shards`` is the model
    # axis size and ``decode_mesh`` the Mesh itself — the fused
    # flash-decode and paged-attention dispatches shard_map their
    # kernels over it (per-rank K/V head slices, replicated block
    # tables).  Distinct from
    # ``model_size``, the TRAINING sequence-parallel degree: serving
    # steps stay single-program per rank and never set model_size.
    decode_shards: int = 1
    decode_mesh: Any = None


DEFAULT_FLAGS = RuntimeFlags()


def constrain_batch(x: jax.Array, flags: RuntimeFlags) -> jax.Array:
    """Pin the leading (batch) dim of an activation to the data axes."""
    if not flags.batch_axes or x.shape[0] % flags.batch_divisor != 0:
        return x
    from jax.sharding import PartitionSpec as P
    spec = P(flags.batch_axes if len(flags.batch_axes) > 1
             else flags.batch_axes[0], *([None] * (x.ndim - 1)))
    return jax.lax.with_sharding_constraint(x, spec)


# ---------------------------------------------------------------------------
# structure
# ---------------------------------------------------------------------------

def group_structure(cfg: ArchConfig):
    """Split layers into (unrolled head, repeating pattern, repeat count)."""
    kinds = list(zip(cfg.layer_kinds(), cfg.ffn_kinds()))
    k = cfg.first_k_dense if cfg.num_experts else 0
    head, rest = kinds[:k], kinds[k:]
    P = len(rest)
    for p in range(1, len(rest) + 1):
        if len(rest) % p == 0 and rest == rest[:p] * (len(rest) // p):
            P = p
            break
    return head, rest[:P], (len(rest) // P if rest else 0)


# ---------------------------------------------------------------------------
# single layer
# ---------------------------------------------------------------------------

def layer_template(cfg: ArchConfig, kind: str, ffn_kind: str,
                   cross: bool = False) -> Template:
    d = cfg.d_model
    t: Template = {"norm1": rmsnorm_template(d)}
    if kind == "attn":
        t["mixer"] = mla_mod.mla_template(cfg) if cfg.use_mla \
            else attn.attention_template(cfg)
    elif kind == "mamba":
        t["mixer"] = mam.mamba_template(cfg)
    elif kind == "mlstm":
        t["mixer"] = xl.mlstm_template(cfg)
    elif kind == "slstm":
        t["mixer"] = xl.slstm_template(cfg)
    else:  # pragma: no cover
        raise ValueError(kind)
    if cross:
        t["cross_norm"] = rmsnorm_template(d)
        t["cross"] = attn.attention_template(
            dataclasses.replace(cfg, qk_norm=False))
    dff = cfg.dense_d_ff if ffn_kind == "dense" else cfg.d_ff
    if dff and not (kind in ("mlstm", "slstm") and cfg.d_ff == 0):
        t["norm2"] = rmsnorm_template(d)
        t["ffn"] = moe_mod.moe_template(cfg) if ffn_kind == "moe" \
            else mlp_template(d, dff)
    return t


def _cross_attention(params, cfg: ArchConfig, x, memory_kv, flags):
    """x: [B,S,d]; memory_kv: dict k/v [B,T,KV,hd] (precomputed)."""
    from .chunked_attention import (chunked_attention,
                                    sequence_parallel_attention)
    q = jnp.einsum("bsd,dhk->bshk", x, params["wq"])
    if flags is not None and getattr(flags, "model_size", 1) > 1:
        out = sequence_parallel_attention(q, memory_kv["k"],
                                          memory_kv["v"], causal=False,
                                          window=0, flags=flags)
    else:
        out = chunked_attention(q, memory_kv["k"], memory_kv["v"],
                                causal=False)
    return jnp.einsum("bshk,hkd->bsd", out, params["wo"])


def cross_kv(params, memory: jax.Array) -> Dict[str, jax.Array]:
    k = jnp.einsum("btd,dhk->bthk", memory, params["wk"])
    v = jnp.einsum("btd,dhk->bthk", memory, params["wv"])
    return {"k": k, "v": v}


def layer_apply(params, cfg: ArchConfig, kind: str, ffn_kind: str,
                x: jax.Array, positions: jax.Array,
                cache: Optional[Dict] = None,
                cache_pos: Optional[jax.Array] = None,
                memory_kv: Optional[Dict] = None,
                flags: RuntimeFlags = DEFAULT_FLAGS,
                want_cache: bool = False, max_cache_len: int = 0,
                block_tables: Optional[jax.Array] = None,
                prefix_kv: Optional[Dict] = None, prefix_len: int = 0,
                state_mask: Optional[jax.Array] = None,
                want_state_stack: bool = False,
                ) -> Tuple[jax.Array, jax.Array, Optional[Dict]]:
    """Returns (x_out, aux_loss, new_cache).

    block_tables: paged decode — ``cache`` holds block-pool arenas.
    prefix_kv/prefix_len: prefix-extend prefill — compute only the prompt
    suffix, attending over K/V gathered for the shared prefix.
    state_mask: [B] bool — rows whose recurrent state may be committed.
    Recurrent mixers overwrite their whole O(1) state on every step, so a
    batched decode step would destroy the checkpointed ingest-frontier
    state of rows that are *not* decoding; masked rows keep their old
    state bit-for-bit (attention K/V needs no mask: stray row writes land
    at that row's frontier and are overwritten on its next real step).
    want_state_stack: recurrent decode windows additionally return the
    state after *every* window position under a ``"stack"`` key of the
    layer cache (and leave the live state uncommitted) — the speculative
    verify/rewind machinery selects the accepted prefix's state out of it.
    """
    with jax.named_scope("norm"):
        h = rms_norm(params["norm1"], x, cfg.norm_eps, flags.fused_rmsnorm)
    new_cache: Dict[str, Any] = {}
    decode = cache is not None
    extend = want_cache and prefix_kv is not None

    def commit_state(c):
        """Apply state_mask / want_state_stack to a recurrent mixer's
        freshly computed state ``c`` (old state: cache["mixer"])."""
        if want_state_stack:
            c = cache["mixer"]                   # truncate() commits later
        elif state_mask is not None:
            c = jax.tree.map(
                lambda nw, od: jnp.where(
                    state_mask.reshape((-1,) + (1,) * (nw.ndim - 1)),
                    nw, od.astype(nw.dtype)),
                c, cache["mixer"])
        return c
    # one named scope per mixer kind, as layer_kinds() names them, kept
    # in each op's metadata (docs/OBSERVABILITY.md, "Layer-kind scopes")
    with jax.named_scope(kind):
        if kind == "attn":
            if cfg.use_mla:
                if decode:
                    y, c = mla_mod.mla_apply(
                        params["mixer"], cfg, h, positions, cache["mixer"],
                        cache_pos, block_tables=block_tables)
                elif extend:
                    y, c = mla_mod.mla_prefill_extend(
                        params["mixer"], cfg, h, positions, prefix_kv["mixer"],
                        prefix_len, max_cache_len, flags=flags)
                elif want_cache:
                    y, c = mla_mod.mla_prefill_into_cache(
                        params["mixer"], cfg, h, positions, max_cache_len,
                        flags=flags)
                else:
                    y, c = mla_mod.mla_apply(params["mixer"], cfg, h,
                                             positions, flags=flags)
            else:
                impl = "flash" if flags.use_flash else flags.attn_impl
                if decode:
                    y, c = attn.attention_apply(params["mixer"], cfg, h,
                                                positions, cache["mixer"],
                                                cache_pos, impl, flags,
                                                block_tables=block_tables)
                elif extend:
                    y, c = attn.prefill_extend_into_cache(
                        params["mixer"], cfg, h, positions, prefix_kv["mixer"],
                        prefix_len, max_cache_len, impl, flags)
                elif want_cache:
                    y, c = attn.prefill_into_cache(
                        params["mixer"], cfg, h, positions, max_cache_len,
                        impl, flags)
                else:
                    y, c = attn.attention_apply(params["mixer"], cfg, h,
                                                positions, impl=impl,
                                                flags=flags)
        elif kind == "mamba":
            if decode:
                if h.shape[1] == 1 and not want_state_stack:
                    y, c = mam.mamba_decode(params["mixer"], cfg, h,
                                            cache["mixer"])
                else:
                    y, c, stk = mam.mamba_window(params["mixer"], cfg, h,
                                                 cache["mixer"],
                                                 want_stack=want_state_stack)
                c = commit_state(c)
            elif want_cache:
                y, c = mam.mamba_prefill_into_cache(params["mixer"], cfg, h)
            else:
                y, c = mam.mamba_apply(params["mixer"], cfg, h)
        elif kind == "mlstm":
            # sequence-parallel scan pays off once S spans many model shards
            use_sp = flags.model_size > 1 and x.shape[1] >= 8192
            if decode:
                if h.shape[1] == 1 and not want_state_stack:
                    y, c = xl.mlstm_decode(params["mixer"], cfg, h,
                                           cache["mixer"])
                else:
                    y, c, stk = xl.mlstm_window(params["mixer"], cfg, h,
                                                cache["mixer"],
                                                want_stack=want_state_stack)
                c = commit_state(c)
            elif use_sp:
                y, c = xl.mlstm_apply_sp(params["mixer"], cfg, h, flags,
                                         want_cache=want_cache)
            elif want_cache:
                y, c = xl.mlstm_prefill_into_cache(params["mixer"], cfg, h)
            else:
                y, c = xl.mlstm_apply(params["mixer"], cfg, h)
        elif kind == "slstm":
            if decode:
                if h.shape[1] == 1 and not want_state_stack:
                    y, c = xl.slstm_decode(params["mixer"], cfg, h,
                                           cache["mixer"])
                else:
                    y, c, stk = xl.slstm_window(params["mixer"], cfg, h,
                                                cache["mixer"],
                                                want_stack=want_state_stack)
                c = commit_state(c)
            elif want_cache:
                y, c = xl.slstm_prefill_into_cache(params["mixer"], cfg, h)
            else:
                y, c = xl.slstm_apply(params["mixer"], cfg, h)
        else:  # pragma: no cover
            raise ValueError(kind)
    new_cache["mixer"] = c
    if want_state_stack and decode:
        # Mirror the layer-cache structure so rewind can tree_map the
        # stack against the live cache; non-recurrent leaves carry a
        # zero-size placeholder.
        if kind in ("mamba", "mlstm", "slstm"):
            new_cache["stack"] = {"mixer": stk}
        else:
            new_cache["stack"] = {"mixer": jax.tree.map(
                lambda _: jnp.zeros((0,), jnp.float32), cache["mixer"])}
    x = x + y

    if "cross" in params and memory_kv is not None:
        with jax.named_scope("norm"):
            hc = rms_norm(params["cross_norm"], x, cfg.norm_eps,
                          flags.fused_rmsnorm)
        with jax.named_scope("attn"):
            x = x + _cross_attention(params["cross"], cfg, hc, memory_kv,
                                     flags)

    aux = jnp.zeros((), jnp.float32)
    if "ffn" in params:
        with jax.named_scope("norm"):
            h2 = rms_norm(params["norm2"], x, cfg.norm_eps,
                          flags.fused_rmsnorm)
        if ffn_kind == "moe":
            with jax.named_scope("ffn.moe"):
                y2, aux = moe_mod.moe_apply(params["ffn"], cfg, h2, flags)
        else:
            with jax.named_scope("ffn"):
                y2 = mlp_apply(params["ffn"], h2)
        x = x + y2
    x = constrain_batch(x, flags)
    return x, aux, (new_cache if (decode or want_cache) else None)


# ---------------------------------------------------------------------------
# whole-model template
# ---------------------------------------------------------------------------

def model_template(cfg: ArchConfig) -> Template:
    d, V = cfg.d_model, cfg.padded_vocab
    t: Template = {
        "embed": embed_template(V, d),
        "final_norm": rmsnorm_template(d),
    }
    if not cfg.tie_embeddings:
        t["lm_head"] = lm_head_template(d, V)
    head, pattern, R = group_structure(cfg)
    if head:
        t["head_layers"] = {f"layer{i}": layer_template(cfg, k, f)
                            for i, (k, f) in enumerate(head)}
    if R:
        group = {f"l{j}": layer_template(
            cfg, k, f, cross=cfg.is_encoder_decoder)
            for j, (k, f) in enumerate(pattern)}
        t["blocks"] = stack_template(group, R)
    if cfg.is_encoder_decoder:
        enc_layer = layer_template(
            dataclasses.replace(cfg, use_mla=False, num_experts=0),
            "attn", "dense")
        t["encoder"] = {
            "blocks": stack_template(enc_layer, cfg.num_encoder_layers),
            "final_norm": rmsnorm_template(d),
        }
    if cfg.mtp_depth:
        t["mtp"] = {
            "proj": ParamSpec((2 * d, d), ("embed_b", "embed")),
            "norm": rmsnorm_template(d),
            "block": layer_template(cfg, "attn",
                                    "dense" if cfg.first_k_dense else
                                    cfg.ffn_kinds()[-1]),
        }
    return t


# ---------------------------------------------------------------------------
# encoder (bidirectional, for enc-dec archs; consumes stub embeddings)
# ---------------------------------------------------------------------------

def encode(params, cfg: ArchConfig, enc_embeds: jax.Array,
           flags: RuntimeFlags) -> jax.Array:
    B, T, d = enc_embeds.shape
    positions = jnp.broadcast_to(jnp.arange(T), (B, T))
    enc_cfg = dataclasses.replace(cfg, use_mla=False, num_experts=0,
                                  sliding_window=0)

    from .chunked_attention import (chunked_attention,
                                    sequence_parallel_attention)

    def step(x, layer_params):
        h = rms_norm(layer_params["norm1"], x, cfg.norm_eps)
        q, k, v = attn._qkv(layer_params["mixer"], enc_cfg, h, positions)
        if getattr(flags, "model_size", 1) > 1:
            o = sequence_parallel_attention(q, k, v, causal=False,
                                            window=0, flags=flags)
        else:
            o = chunked_attention(q, k, v, causal=False)   # bidirectional
        x = x + jnp.einsum("bshk,hkd->bsd", o, layer_params["mixer"]["wo"])
        h2 = rms_norm(layer_params["norm2"], x, cfg.norm_eps)
        x = x + mlp_apply(layer_params["ffn"], h2)
        return constrain_batch(x, flags), None

    fn = jax.checkpoint(step) if flags.remat != "none" else step
    x, _ = jax.lax.scan(lambda c, p: fn(c, p), enc_embeds,
                        params["encoder"]["blocks"])
    return rms_norm(params["encoder"]["final_norm"], x, cfg.norm_eps)


# ---------------------------------------------------------------------------
# forward (train / eval) — full sequence, no cache
# ---------------------------------------------------------------------------

def _logits(params, cfg: ArchConfig, x: jax.Array) -> jax.Array:
    if cfg.tie_embeddings:
        logits = jnp.einsum("bsd,vd->bsv", x, params["embed"]["embedding"])
    else:
        logits = lm_head_apply(params["lm_head"], x)
    if cfg.padded_vocab != cfg.vocab_size:
        # mask pad columns so softmax mass stays on the real vocab
        col = jax.lax.broadcasted_iota(jnp.int32, logits.shape,
                                       logits.ndim - 1)
        logits = jnp.where(col < cfg.vocab_size, logits,
                           jnp.asarray(-1e30, logits.dtype))
    return logits


def forward(params, cfg: ArchConfig, tokens: jax.Array,
            prefix_embeds: Optional[jax.Array] = None,
            enc_embeds: Optional[jax.Array] = None,
            flags: RuntimeFlags = DEFAULT_FLAGS,
            ) -> Tuple[jax.Array, jax.Array, jax.Array]:
    """Returns (logits [B,S,V], aux_loss, final_hidden [B,S,d])."""
    dt = jnp.dtype(cfg.dtype)
    x = embed_apply(params["embed"], tokens, dt)
    if prefix_embeds is not None:
        x = jnp.concatenate([prefix_embeds.astype(dt), x], axis=1)
    x = constrain_batch(x, flags)
    B, S, _ = x.shape
    positions = jnp.broadcast_to(jnp.arange(S), (B, S))
    memory_kv = None
    head, pattern, R = group_structure(cfg)

    aux = jnp.zeros((), jnp.float32)
    if enc_embeds is not None and cfg.is_encoder_decoder:
        memory = encode(params, cfg, enc_embeds, flags)
    else:
        memory = None

    for i in range(len(head)):
        lp = params["head_layers"][f"layer{i}"]
        x, a, _ = layer_apply(lp, cfg, head[i][0], head[i][1], x, positions,
                              flags=flags)
        aux = aux + a

    if R:
        def group_step(carry, group_params):
            x, aux = carry
            for j, (k, f) in enumerate(pattern):
                mkv = cross_kv(group_params[f"l{j}"]["cross"], memory) \
                    if (memory is not None and
                        "cross" in group_params[f"l{j}"]) else None
                x, a, _ = layer_apply(group_params[f"l{j}"], cfg, k, f, x,
                                      positions, memory_kv=mkv, flags=flags)
                aux = aux + a
            return (x, aux), None

        fn = jax.checkpoint(group_step) if flags.remat != "none" \
            else group_step
        (x, aux), _ = jax.lax.scan(fn, (x, aux), params["blocks"])

    x = rms_norm(params["final_norm"], x, cfg.norm_eps, flags.fused_rmsnorm)
    logits = _logits(params, cfg, x)
    return logits, aux, x


def mtp_logits(params, cfg: ArchConfig, hidden: jax.Array,
               tokens: jax.Array, flags: RuntimeFlags = DEFAULT_FLAGS
               ) -> jax.Array:
    """DeepSeek-V3 multi-token prediction head (depth 1): combines the final
    hidden at position t with the embedding of token t+1 to predict t+2."""
    dt = jnp.dtype(cfg.dtype)
    B, S, d = hidden.shape
    nxt = embed_apply(params["embed"], tokens, dt)
    nxt = jnp.concatenate([nxt[:, 1:], jnp.zeros((B, 1, d), dt)], axis=1)
    h = jnp.concatenate([hidden, nxt], axis=-1)
    h = jnp.einsum("bsk,kd->bsd", h, params["mtp"]["proj"])
    h = rms_norm(params["mtp"]["norm"], h, cfg.norm_eps)
    positions = jnp.broadcast_to(jnp.arange(S), (B, S))
    kind = "attn"
    ffn = "dense" if "ffn" in params["mtp"]["block"] and \
        "router" not in params["mtp"]["block"].get("ffn", {}) else "moe"
    h, _, _ = layer_apply(params["mtp"]["block"], cfg, kind, ffn, h,
                          positions, flags=flags)
    return _logits(params, cfg, h)


# ---------------------------------------------------------------------------
# prefill / decode
# ---------------------------------------------------------------------------

def abstract_layer_cache(cfg: ArchConfig, kind: str, batch: int,
                         max_len: int, cross: bool = False,
                         enc_len: int = 0):
    dt = jnp.dtype(cfg.dtype)
    if kind == "attn":
        c = mla_mod.abstract_mla_cache(cfg, batch, max_len, dt) \
            if cfg.use_mla else \
            attn.abstract_kv_cache(cfg, batch, max_len, dt)
    elif kind == "mamba":
        c = mam.abstract_mamba_cache(cfg, batch, dt)
    elif kind == "mlstm":
        c = xl.abstract_mlstm_cache(cfg, batch, dt)
    elif kind == "slstm":
        c = xl.abstract_slstm_cache(cfg, batch, dt)
    else:  # pragma: no cover
        raise ValueError(kind)
    out = {"mixer": c}
    if cross:
        out["cross"] = {
            "k": jax.ShapeDtypeStruct(
                (batch, enc_len, cfg.num_kv_heads, cfg.head_dim), dt),
            "v": jax.ShapeDtypeStruct(
                (batch, enc_len, cfg.num_kv_heads, cfg.head_dim), dt)}
    return out


def abstract_cache(cfg: ArchConfig, batch: int, max_len: int,
                   enc_len: int = 0):
    """ShapeDtypeStruct pytree matching what prefill() returns."""
    head, pattern, R = group_structure(cfg)
    cache: Dict[str, Any] = {}
    cross = cfg.is_encoder_decoder
    if head:
        cache["head_layers"] = {
            f"layer{i}": abstract_layer_cache(cfg, k, batch, max_len)
            for i, (k, f) in enumerate(head)}
    if R:
        group = {f"l{j}": abstract_layer_cache(cfg, k, batch, max_len,
                                               cross, enc_len)
                 for j, (k, f) in enumerate(pattern)}
        cache["blocks"] = jax.tree.map(
            lambda s: jax.ShapeDtypeStruct((R,) + s.shape, s.dtype), group)
    return cache


def check_paged_support(cfg: ArchConfig) -> None:
    """The paged KV cache pages attention K/V; architectures with
    recurrent mixers, sliding windows or cross attention keep using the
    contiguous slot cache."""
    if cfg.is_encoder_decoder:
        raise ValueError("paged KV cache: encoder-decoder models are "
                         "not supported")
    if cfg.sliding_window:
        raise ValueError("paged KV cache: sliding-window attention is "
                         "not supported (the window's rotating slot "
                         "layout conflicts with block paging)")
    bad = [k for k in cfg.layer_kinds() if k != "attn"]
    if bad:
        raise ValueError(f"paged KV cache: recurrent layer kinds "
                         f"{sorted(set(bad))} have O(1) state, not a "
                         f"growing KV cache; use the slot path")


def abstract_paged_cache(cfg: ArchConfig, num_blocks: int, block_size: int):
    """ShapeDtypeStruct pytree of the paged arena (same tree structure as
    :func:`abstract_cache`, with each layer's ``[B, S, ...]`` cache
    replaced by a ``[num_blocks, block_size, ...]`` block pool)."""
    check_paged_support(cfg)
    dt = jnp.dtype(cfg.dtype)
    head, pattern, R = group_structure(cfg)

    def layer(kind: str):
        c = mla_mod.abstract_paged_mla_cache(cfg, num_blocks, block_size,
                                             dt) \
            if cfg.use_mla else \
            attn.abstract_paged_kv_cache(cfg, num_blocks, block_size, dt)
        return {"mixer": c}

    cache: Dict[str, Any] = {}
    if head:
        cache["head_layers"] = {f"layer{i}": layer(k)
                                for i, (k, f) in enumerate(head)}
    if R:
        group = {f"l{j}": layer(k) for j, (k, f) in enumerate(pattern)}
        cache["blocks"] = jax.tree.map(
            lambda s: jax.ShapeDtypeStruct((R,) + s.shape, s.dtype), group)
    return cache


def check_hybrid_support(cfg: ArchConfig) -> None:
    """The hybrid layout pages attention K/V and keeps recurrent layers
    in O(1) state slabs; the per-layer composition rules out the same
    attention variants the paged arena does."""
    if cfg.is_encoder_decoder:
        raise ValueError("hybrid cache: encoder-decoder models are not "
                         "supported")
    if cfg.sliding_window:
        raise ValueError("hybrid cache: sliding-window attention is not "
                         "supported (the window's rotating slot layout "
                         "conflicts with block paging)")


def abstract_hybrid_cache(cfg: ArchConfig, num_slots: int, num_blocks: int,
                          block_size: int):
    """ShapeDtypeStruct pytree of the hybrid layout: per layer, attention
    K/V lives in a ``[num_blocks, block_size, ...]`` block-pool arena
    (reached through block tables, exactly the paged layout) while
    recurrent mixers live in ``[num_slots, ...]`` state slabs (slot i of
    every slab belongs to the request in scheduler slot i)."""
    check_hybrid_support(cfg)
    dt = jnp.dtype(cfg.dtype)
    head, pattern, R = group_structure(cfg)

    def layer(kind: str):
        if kind == "attn":
            c = mla_mod.abstract_paged_mla_cache(cfg, num_blocks,
                                                 block_size, dt) \
                if cfg.use_mla else \
                attn.abstract_paged_kv_cache(cfg, num_blocks, block_size, dt)
            return {"mixer": c}
        return abstract_layer_cache(cfg, kind, num_slots, 0)

    cache: Dict[str, Any] = {}
    if head:
        cache["head_layers"] = {f"layer{i}": layer(k)
                                for i, (k, f) in enumerate(head)}
    if R:
        group = {f"l{j}": layer(k) for j, (k, f) in enumerate(pattern)}
        cache["blocks"] = jax.tree.map(
            lambda s: jax.ShapeDtypeStruct((R,) + s.shape, s.dtype), group)
    return cache


def layer_kind_of_path(cfg: ArchConfig, path) -> str:
    """Map a cache-pytree key path (as produced by
    ``jax.tree_util.tree_map_with_path``) to its layer kind — the one
    dispatch point mixed-layout cache writers need to decide whether a
    leaf is a paged attention arena or a recurrent state slab."""
    head, pattern, _ = group_structure(cfg)
    k0 = getattr(path[0], "key", None)
    if k0 == "head_layers":
        return head[int(path[1].key[len("layer"):])][0]
    if k0 == "blocks":
        return pattern[int(path[1].key[1:])][0]
    raise KeyError(f"not a layer cache path: {path}")


def prefill(params, cfg: ArchConfig, tokens: jax.Array, max_cache_len: int,
            prefix_embeds: Optional[jax.Array] = None,
            enc_embeds: Optional[jax.Array] = None,
            flags: RuntimeFlags = DEFAULT_FLAGS):
    """Run the prompt, return (last-token logits [B,V], cache)."""
    dt = jnp.dtype(cfg.dtype)
    with jax.named_scope("embed"):
        x = embed_apply(params["embed"], tokens, dt)
    if prefix_embeds is not None:
        x = jnp.concatenate([prefix_embeds.astype(dt), x], axis=1)
    x = constrain_batch(x, flags)
    B, S, _ = x.shape
    positions = jnp.broadcast_to(jnp.arange(S), (B, S))
    head, pattern, R = group_structure(cfg)
    memory = encode(params, cfg, enc_embeds, flags) \
        if (enc_embeds is not None and cfg.is_encoder_decoder) else None

    cache: Dict[str, Any] = {}
    if head:
        cache["head_layers"] = {}
        for i, (k, f) in enumerate(head):
            lp = params["head_layers"][f"layer{i}"]
            x, _, c = layer_apply(lp, cfg, k, f, x, positions,
                                  want_cache=True, max_cache_len=max_cache_len,
                                  flags=flags)
            cache["head_layers"][f"layer{i}"] = c
    if R:
        def group_step(x, group_params):
            caches = {}
            for j, (k, f) in enumerate(pattern):
                lp = group_params[f"l{j}"]
                mkv = cross_kv(lp["cross"], memory) \
                    if (memory is not None and "cross" in lp) else None
                x, _, c = layer_apply(lp, cfg, k, f, x, positions,
                                      memory_kv=mkv, want_cache=True,
                                      max_cache_len=max_cache_len,
                                      flags=flags)
                if mkv is not None:
                    c["cross"] = mkv
                caches[f"l{j}"] = c
            return x, caches

        x, group_caches = jax.lax.scan(group_step, x, params["blocks"])
        cache["blocks"] = group_caches

    with jax.named_scope("head"):
        x = rms_norm(params["final_norm"], x, cfg.norm_eps,
                     flags.fused_rmsnorm)
        logits = _logits(params, cfg, x[:, -1:, :])[:, 0]
    return logits, cache


def check_mixed_extend_support(cfg: ArchConfig) -> None:
    """Prefix-extend limits that hold on *any* cache layout (per-layer
    checks — paged attention arenas add :func:`check_paged_support` on
    top, via the engine's layout gates)."""
    if cfg.is_encoder_decoder:
        raise ValueError("prefix extend: encoder-decoder models are not "
                         "supported")
    if cfg.sliding_window and "attn" in cfg.layer_kinds():
        raise ValueError("prefix extend: sliding-window attention is not "
                         "supported (the rotating slot layout has no "
                         "stable prefix rows)")


def prefill_extend(params, cfg: ArchConfig, tokens: jax.Array,
                   cache, prefix_ref, prefix_len: int,
                   max_cache_len: int,
                   flags: RuntimeFlags = DEFAULT_FLAGS,
                   slots: Optional[jax.Array] = None):
    """Prefill a prompt *suffix* against already-cached prefix K/V.

    tokens: [B, S'] — the prompt tokens from position ``prefix_len`` on.
    ``prefix_ref`` names where the prefix lives
    (:class:`~repro.models.paging.PagedPrefix` — block-pool arena through
    a block table, ``prefix_len`` a static multiple of its block size —
    or :class:`~repro.models.paging.SlotPrefix` — contiguous slot rows).
    This one entry point serves both prefix-shared prefill and chunked
    prefill on any cache layout.  Attention layers attend over gathered
    prefix K/V and emit suffix cache rows padded to ``max_cache_len``;
    recurrent layers (mamba/mlstm/slstm) instead *continue the sequential
    state scan* from their slab rows at ``slots`` ([B] int32, required
    for such stacks) and emit the state after the last suffix token —
    write both back with the layout's insert.  Returns (last-token
    logits [B, V], per-layer outputs).  Suffix activations are
    bit-identical to a cold prefill of the full prompt (row-independent
    attention, chunk-invariant sequential state scans)."""
    check_mixed_extend_support(cfg)
    dt = jnp.dtype(cfg.dtype)
    x = embed_apply(params["embed"], tokens, dt)
    x = constrain_batch(x, flags)
    B, S_, _ = x.shape
    positions = jnp.broadcast_to(prefix_len + jnp.arange(S_), (B, S_))

    def gather_prefix(mixer_cache):
        return paging.gather_prefix_kv(mixer_cache, prefix_ref, prefix_len)

    def apply_layer(lp, k, f, x, arena_layer):
        if k == "attn":
            pkv = {"mixer": gather_prefix(arena_layer["mixer"])}
            return layer_apply(lp, cfg, k, f, x, positions,
                               want_cache=True,
                               max_cache_len=max_cache_len, flags=flags,
                               prefix_kv=pkv, prefix_len=prefix_len)
        # recurrent: resume the state scan from the slab rows
        init = {"mixer": jax.tree.map(lambda a: a[slots],
                                      arena_layer["mixer"])}
        return layer_apply(lp, cfg, k, f, x, positions, cache=init,
                           cache_pos=positions[:, 0], flags=flags)

    head, pattern, R = group_structure(cfg)
    out_cache: Dict[str, Any] = {}
    if head:
        out_cache["head_layers"] = {}
        for i, (k, f) in enumerate(head):
            lp = params["head_layers"][f"layer{i}"]
            x, _, c = apply_layer(lp, k, f, x,
                                  cache["head_layers"][f"layer{i}"])
            out_cache["head_layers"][f"layer{i}"] = c
    if R:
        def group_step(x, scanned):
            group_params, group_arena = scanned
            caches = {}
            for j, (k, f) in enumerate(pattern):
                x, _, c = apply_layer(group_params[f"l{j}"], k, f, x,
                                      group_arena[f"l{j}"])
                caches[f"l{j}"] = c
            return x, caches

        x, group_caches = jax.lax.scan(group_step, x,
                                       (params["blocks"], cache["blocks"]))
        out_cache["blocks"] = group_caches

    x = rms_norm(params["final_norm"], x, cfg.norm_eps, flags.fused_rmsnorm)
    logits = _logits(params, cfg, x[:, -1:, :])[:, 0]
    return logits, out_cache


def decode_step(params, cfg: ArchConfig, tokens: jax.Array,
                cache, cache_pos: jax.Array,
                flags: RuntimeFlags = DEFAULT_FLAGS,
                block_tables: Optional[jax.Array] = None,
                all_logits: bool = False,
                state_mask: Optional[jax.Array] = None,
                want_state_stacks: bool = False):
    """One decode step. tokens: [B, S'] (S' = 1 for plain decode; S' > 1
    scores a speculative verify window — the last emitted token plus
    drafted continuations — in one pass).  Returns (logits, new_cache):
    logits are [B, V] at the first window position by default, or
    [B, S', V] at every window position with ``all_logits=True`` (the
    speculative verification read-out).

    ``cache_pos`` is either a scalar (all rows at the same offset — the
    classic static batch) or a [B] vector of per-row offsets (continuous
    batching: every row is an independent request/slot); window token s
    of row b sits at absolute position ``cache_pos[b] + s``.

    ``block_tables`` ([B, P] int32) switches to the paged path: ``cache``
    holds block-pool arenas and each row's K/V is reached through its
    block table (bit-identical greedy tokens to the contiguous path).

    ``state_mask`` / ``want_state_stacks`` serve recurrent/hybrid cache
    layouts (see :func:`layer_apply`); with ``want_state_stacks`` the
    return becomes (logits, new_cache, stacks) where ``stacks`` mirrors
    the cache tree, each recurrent state leaf grown to [..., S', ...]
    (state after every window position) and every other leaf a
    zero-size placeholder."""
    dt = jnp.dtype(cfg.dtype)
    with jax.named_scope("embed"):
        x = embed_apply(params["embed"], tokens, dt)
    x = constrain_batch(x, flags)
    B, S_q = x.shape[0], x.shape[1]
    cache_pos = jnp.asarray(cache_pos, jnp.int32)
    positions = cache_pos[:, None] + jnp.arange(S_q)[None, :] \
        if cache_pos.ndim == 1 \
        else jnp.broadcast_to(cache_pos + jnp.arange(S_q), (B, S_q))
    head, pattern, R = group_structure(cfg)

    new_cache: Dict[str, Any] = {}
    stacks: Dict[str, Any] = {}
    if head:
        new_cache["head_layers"] = {}
        stacks["head_layers"] = {}
        for i, (k, f) in enumerate(head):
            lp = params["head_layers"][f"layer{i}"]
            x, _, c = layer_apply(lp, cfg, k, f, x, positions,
                                  cache=cache["head_layers"][f"layer{i}"],
                                  cache_pos=cache_pos, flags=flags,
                                  block_tables=block_tables,
                                  state_mask=state_mask,
                                  want_state_stack=want_state_stacks)
            if want_state_stacks:
                stacks["head_layers"][f"layer{i}"] = c.pop("stack")
            new_cache["head_layers"][f"layer{i}"] = c
    if R:
        # The stacked cache rides in the scan CARRY (updated in place per
        # layer group with dynamic_update_index) rather than as xs/ys — XLA
        # then keeps ONE cache buffer alive instead of separate in/out
        # copies, halving decode HBM (EXPERIMENTS.md §Perf).
        blocks_cache = cache["blocks"]

        def group_step(carry, scanned):
            x, blocks_cache = carry
            group_params, idx = scanned
            with jax.named_scope("cache"):
                group_cache = jax.tree.map(
                    lambda a: jax.lax.dynamic_index_in_dim(
                        a, idx, 0, keepdims=False),
                    blocks_cache)
            new_group = {}
            group_stacks = {}
            for j, (k, f) in enumerate(pattern):
                lp = group_params[f"l{j}"]
                mkv = group_cache[f"l{j}"].get("cross")
                x, _, c = layer_apply(lp, cfg, k, f, x, positions,
                                      cache=group_cache[f"l{j}"],
                                      cache_pos=cache_pos,
                                      memory_kv=mkv, flags=flags,
                                      block_tables=block_tables,
                                      state_mask=state_mask,
                                      want_state_stack=want_state_stacks)
                if want_state_stacks:
                    group_stacks[f"l{j}"] = c.pop("stack")
                if mkv is not None:
                    c["cross"] = mkv
                new_group[f"l{j}"] = c
            with jax.named_scope("cache"):
                blocks_cache = jax.tree.map(
                    lambda full, new: jax.lax.dynamic_update_index_in_dim(
                        full, new.astype(full.dtype), idx, 0),
                    blocks_cache, new_group)
            return (x, blocks_cache), group_stacks

        (x, blocks_cache), block_stacks = jax.lax.scan(
            group_step, (x, blocks_cache),
            (params["blocks"], jnp.arange(R)))
        new_cache["blocks"] = blocks_cache
        if want_state_stacks:
            stacks["blocks"] = block_stacks

    with jax.named_scope("head"):
        x = rms_norm(params["final_norm"], x, cfg.norm_eps,
                     flags.fused_rmsnorm)
        logits = _logits(params, cfg, x)
    logits = logits if all_logits else logits[:, 0]
    if want_state_stacks:
        return logits, new_cache, stacks
    return logits, new_cache
