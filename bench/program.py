"""The system under test, built from a configuration file.

A configuration file states the model in the keys of its published
``config.json``, with the values that are run.  This module maps them
onto the repository's ``ArchConfig`` (starting from the preset the file
names under ``program.arch``) and refuses a file the program cannot run
as stated.  It also compiles nothing and allocates nothing: the harness
makes the weights and the server.
"""
from __future__ import annotations

import dataclasses
from typing import Any, Dict

#: published key -> ArchConfig field
FIELDS = {
    "num_hidden_layers": "num_layers",
    "hidden_size": "d_model",
    "num_attention_heads": "num_heads",
    "num_key_value_heads": "num_kv_heads",
    "head_dim": "head_dim",
    "intermediate_size": "d_ff",
    "vocab_size": "vocab_size",
    "tie_word_embeddings": "tie_embeddings",
    "rms_norm_eps": "norm_eps",
    "rope_theta": "rope_theta",
    "torch_dtype": "dtype",
}


def _check_runnable(c: Dict[str, Any]) -> None:
    """The program runs SwiGLU blocks only.  (MiniCPM's muP scalings are
    folded into the weights it is handed: ``weights.for_program``.)"""
    if c.get("hidden_act", "silu") != "silu":
        raise ValueError(f"hidden_act {c['hidden_act']!r}: the program "
                         f"runs SwiGLU (silu) only")


def arch_config(config: Dict[str, Any]):
    """The ``ArchConfig`` that runs ``config`` (a configuration file's
    contents)."""
    from repro.configs import get_config
    c = config["config"]
    _check_runnable(c)
    base = get_config(config["program"]["arch"])
    over = {field: c[key] for key, field in FIELDS.items() if key in c}
    if "head_dim" not in over:
        over["head_dim"] = c["hidden_size"] // c["num_attention_heads"]
    return dataclasses.replace(base, **over)
