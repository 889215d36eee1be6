"""Random weights from a seed, made on the device in one jitted call.

:func:`make` draws the model: every projection at std 1/sqrt(fan-in
within one layer): a projection's input width, and heads x head_dim
for the attention output.  (The repository's ``Model.init`` divides a
layer-stacked matrix by sqrt(num_layers) instead, which at published
widths puts bf16 logits far from float32 ones and leaves no comparison
that can fail.)  Embeddings are drawn at std 0.02 / ``scale_emb``, so
that the scaled embedding enters the residual stream at std 0.02 as in
a model without muP: at 0.02 itself, times MiniCPM's 12, it would
outweigh the residual branches (scaled by 1.4 / sqrt(40)) so far that
the random model copies its input token through the tied head with a
margin no rounding can flip, and no fault would show.  Norm scales are
drawn at 1 + N(0, ``NORM_STD``), so that a norm scale skipped, or read
in the place of another, moves the logits.  The leaves follow the
program's parameter tree, read from its abstract shapes.

:func:`for_program` turns the model into the weights the program is
handed.  The program runs a plain pre-norm Llama block; MiniCPM's muP
scalings (``scale_emb``, ``scale_depth``, ``dim_model_base``) are
linear, so they are folded into the weights exactly: the embedding
times ``scale_emb``, each block's attention output and down projection
times ``scale_depth / sqrt(num_hidden_layers)``, and the logits'
division by ``hidden_size / dim_model_base`` into the final norm's
scale (tied head, which also carries ``scale_emb``) or into the head.
The plain reference reads the model as drawn and applies the published
equations itself.
"""
from __future__ import annotations

import math
from typing import Any, Dict, Optional

import numpy as np

#: standard deviation of a norm scale around 1
NORM_STD = 0.25


def key_for(seed: int, stream: int):
    """A JAX PRNG key from a seed of any size (the legacy key would keep
    only its low 32 bits)."""
    import jax.numpy as jnp
    state = np.random.SeedSequence([int(seed), stream]).generate_state(2)
    return jnp.asarray(state, jnp.uint32)


def leaf_init(path: tuple, shape: tuple, embed_std: float = 0.02) -> tuple:
    """How one leaf is drawn: ``("norm", std)`` for 1 + N(0, std), or
    ``("normal", std)``."""
    names = [str(getattr(k, "key", k)) for k in path]
    last = names[-1]
    if last == "scale":
        return "norm", NORM_STD
    if last == "embedding":
        return "normal", embed_std
    stacked = "blocks" in names
    inner = shape[1:] if stacked else shape
    if last == "wo":
        fan_in = int(np.prod(inner[:-1]))
    else:
        fan_in = int(inner[0])
    return "normal", 1.0 / math.sqrt(fan_in)


def make(abstract_params, seed: int,
         config: Optional[Dict[str, Any]] = None):
    """Materialise ``abstract_params`` (a pytree of ShapeDtypeStructs)
    on the default device, in their dtype, in one jitted call, for the
    configuration file ``config`` (its ``scale_emb`` sets the embedding's
    std)."""
    import jax
    import jax.numpy as jnp

    embed_std = 0.02
    if config is not None:
        embed_std /= mup_factors(config)["emb"]
    flat, treedef = jax.tree_util.tree_flatten_with_path(abstract_params)
    inits = [leaf_init(p, s.shape, embed_std) for p, s in flat]

    def build(key):
        keys = jax.random.split(key, len(flat))
        out = []
        for (path, spec), (kind, std), k in zip(flat, inits, keys):
            x = jax.random.normal(k, spec.shape, jnp.float32) * std
            out.append((1.0 + x if kind == "norm" else x).astype(spec.dtype))
        return jax.tree_util.tree_unflatten(treedef, out)

    return jax.jit(build)(key_for(seed, 7))


def mup_factors(config: Dict[str, Any]) -> Dict[str, float]:
    """The published muP scalings of a configuration file as factors:
    ``emb`` on the embedding, ``residual`` on each residual branch,
    ``logits`` on the logits.  All 1 for a model without them."""
    c = config["config"]
    return {"emb": float(c.get("scale_emb", 1.0)),
            "residual": float(c.get("scale_depth",
                                    math.sqrt(c["num_hidden_layers"])))
            / math.sqrt(c["num_hidden_layers"]),
            "logits": float(c.get("dim_model_base", c["hidden_size"]))
            / float(c["hidden_size"])}


def folds(config: Dict[str, Any]) -> bool:
    return any(v != 1.0 for v in mup_factors(config).values())


def for_program(params, config: Dict[str, Any]):
    """The weights the program runs, with the muP scalings folded in
    (``params`` itself when there are none; otherwise its buffers are
    donated)."""
    if not folds(config):
        return params
    import jax
    f = mup_factors(config)
    tied = "lm_head" not in params

    def fold(p):
        p = jax.tree.map(lambda a: a, p)        # a fresh tree to edit
        dt = lambda a, s: (a.astype("float32") * s).astype(a.dtype)  # noqa
        p["embed"] = {**p["embed"],
                      "embedding": dt(p["embed"]["embedding"], f["emb"])}
        blk = p["blocks"]["l0"]
        blk["mixer"] = {**blk["mixer"],
                        "wo": dt(blk["mixer"]["wo"], f["residual"])}
        blk["ffn"] = {**blk["ffn"],
                      "w_down": dt(blk["ffn"]["w_down"], f["residual"])}
        if tied:
            head = f["logits"] / f["emb"]
            p["final_norm"] = {"scale": dt(p["final_norm"]["scale"], head)}
        else:
            p["lm_head"] = {**p["lm_head"],
                            "w": dt(p["lm_head"]["w"], f["logits"])}
        return p

    return jax.jit(fold, donate_argnums=0)(params)
