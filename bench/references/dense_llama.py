"""Plain reference of a dense decoder of the Llama family, in float32.

Written from the published description (pre-norm RMSNorm blocks,
rotary position embedding on the two halves of each head, causal
softmax attention, SwiGLU feed-forward, RMSNorm before the vocabulary
head, the head tied to the embedding or not) and importing nothing of
the system under test.  MiniCPM's muP scalings apply where the
configuration states them, as its modelling code does: the embedding
times ``scale_emb``, each residual branch times
``scale_depth / sqrt(num_hidden_layers)``, the logits divided by
``hidden_size / dim_model_base``.  It reads the weights the benchmark
drew from the seed (``weights.make``, before any folding), laid out as
the served parameter tree:

    embed/embedding [V', d]        lm_head/w [d, V']  (untied only)
    final_norm/scale [d]
    blocks/l0/norm1/scale [L, d]   blocks/l0/norm2/scale [L, d]
    blocks/l0/mixer/wq [L, d, H, hd]   wk, wv [L, d, KV, hd]
    blocks/l0/mixer/wo [L, H, hd, d]
    blocks/l0/ffn/w_gate, w_up [L, d, F]   w_down [L, F, d]

(``V'`` may pad the vocabulary; only the first ``vocab_size`` columns
are logits.)  Layers run one at a time under ``lax.scan`` with each
layer's bf16 weights cast to float32 inside the step, and attention runs
in blocks of queries, so the whole model never sits on the chip in
float32.  Matrix products use ``Precision.HIGHEST``.

``mode="fp8"`` is the control: every projection's input and weight are
rounded to float8 (e4m3) with a scale per token row and per weight
matrix, the step below the bf16 the configurations state.
"""
from __future__ import annotations

from typing import Any, Dict

import jax
import jax.numpy as jnp
import numpy as np

HIGHEST = jax.lax.Precision.HIGHEST
QUERY_BLOCK = 512
F8 = jnp.float8_e4m3fn
F8_MAX = 448.0


def _quant(x, axis):
    scale = jnp.max(jnp.abs(x), axis=axis, keepdims=True) / F8_MAX
    scale = jnp.where(scale > 0, scale, 1.0)
    return (x / scale).astype(F8).astype(jnp.float32) * scale


def _proj(x, w, spec, mode):
    """``einsum(spec, x, w)`` in float32; in fp8 mode both operands are
    rounded to e4m3 first (activations per row, the weight per matrix)."""
    if mode == "fp8":
        x = _quant(x, axis=-1)
        w = _quant(w, axis=None)
    return jnp.einsum(spec, x, w, precision=HIGHEST)


def _rms(x, scale, eps):
    var = jnp.mean(x * x, axis=-1, keepdims=True)
    return x * jax.lax.rsqrt(var + eps) * scale


def _rope(x, pos, theta):
    """x [S, H, hd]; rotate the pair (x[i], x[i + hd/2]) by
    pos * theta^(-2i/hd)."""
    hd = x.shape[-1]
    inv = 1.0 / theta ** (jnp.arange(0, hd, 2, dtype=jnp.float32) / hd)
    ang = pos[:, None].astype(jnp.float32) * inv[None, :]
    cos, sin = jnp.cos(ang)[:, None, :], jnp.sin(ang)[:, None, :]
    x1, x2 = x[..., :hd // 2], x[..., hd // 2:]
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], -1)


def _attention(q, k, v):
    """Causal attention of q [S, H, hd] over k, v [S, KV, hd], in blocks
    of queries."""
    S, H, hd = q.shape
    group = H // k.shape[1]
    k = jnp.repeat(k, group, axis=1)
    v = jnp.repeat(v, group, axis=1)
    nb = S // QUERY_BLOCK
    qb = q.reshape(nb, QUERY_BLOCK, H, hd)
    keys = jnp.arange(S)

    def block(args):
        i, qi = args
        rows = i * QUERY_BLOCK + jnp.arange(QUERY_BLOCK)
        s = jnp.einsum("qhd,khd->hqk", qi, k,
                       precision=HIGHEST) / jnp.sqrt(jnp.float32(hd))
        s = jnp.where(keys[None, None, :] <= rows[None, :, None], s,
                      -jnp.inf)
        p = jax.nn.softmax(s, axis=-1)
        return jnp.einsum("hqk,khd->qhd", p, v, precision=HIGHEST)

    out = jax.lax.map(block, (jnp.arange(nb), qb))
    return out.reshape(S, H, hd)


def build(config: Dict[str, Any], length: int, rows: int, mode: str = "f32"):
    """A jitted ``logits(params, tokens [length], at [rows])`` giving the
    float32 logits [rows, vocab_size] that follow positions ``at`` of the
    token sequence.  ``length`` must be a multiple of 512."""
    c = config["config"]
    eps = float(c["rms_norm_eps"])
    theta = float(c.get("rope_theta", 10000.0))
    vocab = int(c["vocab_size"])
    tied = bool(c.get("tie_word_embeddings", False))
    layers = int(c["num_hidden_layers"])
    scale_emb = float(c.get("scale_emb", 1.0))
    residual = float(c.get("scale_depth", np.sqrt(layers))) / np.sqrt(layers)
    logit_div = float(c["hidden_size"]) / float(
        c.get("dim_model_base", c["hidden_size"]))
    if length % QUERY_BLOCK:
        raise ValueError(f"length {length} is not a multiple of "
                         f"{QUERY_BLOCK}")
    f32 = lambda a: a.astype(jnp.float32)  # noqa: E731

    def logits(params, tokens, at):
        emb = params["embed"]["embedding"]
        x = f32(emb[tokens]) * scale_emb
        pos = jnp.arange(length)
        layer = params["blocks"]["l0"]

        def step(x, lp):
            lp = jax.tree.map(f32, lp)
            m, f = lp["mixer"], lp["ffn"]
            h = _rms(x, lp["norm1"]["scale"], eps)
            q = _rope(_proj(h, m["wq"], "sd,dhk->shk", mode), pos, theta)
            k = _rope(_proj(h, m["wk"], "sd,dhk->shk", mode), pos, theta)
            v = _proj(h, m["wv"], "sd,dhk->shk", mode)
            o = _attention(q, k, v)
            x = x + residual * _proj(o.reshape(length, -1),
                                     m["wo"].reshape(-1, m["wo"].shape[-1]),
                                     "sk,kd->sd", mode)
            h = _rms(x, lp["norm2"]["scale"], eps)
            g = _proj(h, f["w_gate"], "sd,df->sf", mode)
            u = _proj(h, f["w_up"], "sd,df->sf", mode)
            x = x + residual * _proj(jax.nn.silu(g) * u, f["w_down"],
                                     "sf,fd->sd", mode)
            return x, None

        x, _ = jax.lax.scan(step, x, layer)
        h = _rms(x[at], f32(params["final_norm"]["scale"]), eps)
        head = f32(emb[:vocab]).T if tied else \
            f32(params["lm_head"]["w"][:, :vocab])
        return _proj(h, head, "sd,dv->sv", mode) / logit_div

    return jax.jit(logits)


def pad_to(n: int) -> int:
    return -(-n // QUERY_BLOCK) * QUERY_BLOCK


def sequence(prompt: np.ndarray, served: np.ndarray, length: int):
    """Token array and read-out positions for one served request: the
    prompt and every served token but the last, padded to ``length``;
    the logits after position ``len(prompt) - 1 + i`` score served token
    ``i``."""
    seq = np.zeros(length, np.int32)
    full = np.concatenate([prompt, served[:-1]]).astype(np.int32)
    seq[:len(full)] = full
    return seq, len(prompt) - 1 + np.arange(len(served))
