"""The weights the benchmark draws, and the muP fold: the program run
on folded weights computes the published MiniCPM equations, which the
plain reference applies to the weights as drawn."""
import copy
import json
from pathlib import Path

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from bench import spec, weights
from bench.program import arch_config

DATA = Path(__file__).parent / "data"
LENGTH = 512


def tiny(**mup):
    config = json.loads((DATA / "tiny.json").read_text())
    config["config"].update(mup)
    return config


MUP = {"scale_emb": 12, "dim_model_base": 32, "scale_depth": 1.4}


def logits_pair(config, seed=2**31 + 5):
    """(program logits on the folded weights, reference logits on the
    weights as drawn) over one random sequence."""
    from repro.models.model import Model
    model = Model(arch_config(config))
    drawn = weights.make(model.abstract(), seed, config)
    served = weights.for_program(copy.deepcopy(drawn), config)
    vocab = config["config"]["vocab_size"]
    tokens = np.random.default_rng(seed).integers(0, vocab, LENGTH,
                                                  dtype=np.int32)
    out = model.forward(served, jnp.asarray(tokens)[None])
    prog = np.asarray(out[0] if isinstance(out, tuple) else out,
                      np.float32)[0, :, :vocab]
    ref = spec.load_reference(config["reference"])
    fn = ref.build(config, LENGTH, LENGTH, "f32")
    return prog, np.asarray(fn(drawn, tokens, np.arange(LENGTH,
                                                         dtype=np.int32)))


def test_norm_scales_are_drawn_around_one():
    abstract = {"n": {"scale": jax.ShapeDtypeStruct((4096,), jnp.float32)},
                "w": jax.ShapeDtypeStruct((64, 64), jnp.float32)}
    p = weights.make(abstract, 3)
    s = np.asarray(p["n"]["scale"])
    assert abs(s.mean() - 1) < 0.02
    assert abs(s.std() - weights.NORM_STD) < 0.02
    assert abs(float(np.asarray(p["w"]).std()) - 1 / 8) < 0.01


def test_same_seed_same_weights():
    abstract = {"w": jax.ShapeDtypeStruct((8, 8), jnp.bfloat16)}
    a, b = weights.make(abstract, 2**40 + 1), weights.make(abstract,
                                                            2**40 + 1)
    c = weights.make(abstract, 2**40 + 2)
    assert np.array_equal(np.asarray(a["w"]), np.asarray(b["w"]))
    assert not np.array_equal(np.asarray(a["w"]), np.asarray(c["w"]))


def test_nothing_is_folded_without_mup():
    config = tiny()
    assert not weights.folds(config)
    p = {"w": jnp.ones(3)}
    assert weights.for_program(p, config) is p


@pytest.mark.parametrize("mup", [MUP, {"scale_emb": 12}],
                         ids=["all", "embedding"])
def test_folded_program_matches_the_published_equations(mup):
    prog, ref = logits_pair(tiny(**mup))
    scale = np.abs(ref).max()
    assert np.abs(prog - ref).max() < 0.05 * scale


def test_a_program_without_the_fold_does_not_match():
    config = tiny(**MUP)
    from repro.models.model import Model
    model = Model(arch_config(config))
    seed = 2**31 + 5
    drawn = weights.make(model.abstract(), seed, config)
    vocab = config["config"]["vocab_size"]
    tokens = np.random.default_rng(seed).integers(0, vocab, LENGTH,
                                                  dtype=np.int32)
    out = model.forward(drawn, jnp.asarray(tokens)[None])
    unfolded = np.asarray(out[0] if isinstance(out, tuple) else out,
                          np.float32)[0, :, :vocab]
    _, ref = logits_pair(config, seed)
    assert np.abs(unfolded - ref).max() > 0.5 * np.abs(ref).max()
