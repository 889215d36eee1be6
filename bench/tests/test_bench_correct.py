"""The check that decides ``correct``, end to end at a size a CPU holds.

A two-layer cut of the MiniCPM-2B configuration (``data/tiny.json``)
is served through the harness's whole run (weights from the seed,
``GraphServer`` behind ``AsyncFrontend``, the open-loop window, the
reference over the sample) with the device check skipped.  A sound run
is correct; a run with the timed path broken underneath is not, for
each fault a served cell can have; and the fp8 control reads far above
the program."""
import json
import time
from pathlib import Path

import numpy as np
import pytest

from bench import harness, spec

DATA = Path(__file__).parent / "data"
LIMITS = {"sample_requests": 4, "min_tokens_compared": 16,
          "max_logit_gap": 0.02}
SEED = 2**31 + 101
SECONDS = 1.5


def tiny_cell():
    full = spec.load_cell("minicpm-2b.chat")
    return spec.Cell("tiny.chat", 1,
                     json.loads((DATA / "tiny.json").read_text()),
                     json.loads((DATA / "tiny_mix.json").read_text()),
                     dict(LIMITS), full.metrics)


def run(fault=None):
    return harness.run_cell(tiny_cell(), SEED, SECONDS, False,
                            t_start=time.perf_counter(), require_tpu=False,
                            peaks={}, fault=fault, log=lambda *a: None)


def altered_tokens(engine):
    """A token altered where it is produced: every decode step hands the
    scheduler the runner-up's neighbour instead of its argmax."""
    decode = engine.decode

    def broken(backend, cache, last, positions, active,
               block_tables=None):
        out, cache = decode(backend, cache, last, positions, active,
                            block_tables)
        vocab = engine.cfg.vocab_size
        return np.where(active, (out + 1) % vocab, out), cache

    engine.decode = broken


def state_unchanged(engine):
    """A step that returns its state unchanged: decode computes its
    token but hands back the cache it was given, so no K/V is written."""
    decode = engine.decode

    def broken(backend, cache, last, positions, active,
               block_tables=None):
        out, _ = decode(backend, cache, last, positions, active,
                        block_tables)
        return out, cache

    engine.decode = broken


def norms_swapped(engine):
    """A norm scale read in the place of another: every block's
    pre-attention norm gets the pre-feed-forward norm's scale and the
    other way round."""
    layer = engine.params["blocks"]["l0"]
    layer["norm1"], layer["norm2"] = layer["norm2"], layer["norm1"]


def test_a_sound_run_is_correct():
    res = run()
    assert res["correct"], res["checks"]
    assert res["failed"] == 0
    assert res["checks"]["tokens_compared"]["value"] >= 16
    assert set(res["metrics"]) == {"itl_p90_ms", "setup_s"}
    assert list(res)[-1] == "checks"


@pytest.mark.parametrize("fault", [altered_tokens, state_unchanged,
                                   norms_swapped])
def test_a_broken_timed_path_is_not_correct(fault):
    res = run(fault)
    assert not res["correct"]
    gap = res["checks"]["max_logit_gap"]
    assert gap["value"] > gap["limit"]


def test_the_fp8_control_fails_the_limit():
    cell = tiny_cell()
    t = time.perf_counter()
    setup = harness.prepare(cell, SEED, trace=False, t_start=t,
                            require_tpu=False, peaks={},
                            log=lambda *a: None)
    out = harness.serve(setup, SEED, SECONDS, t_start=t,
                        log=lambda *a: None)
    _, gaps = harness.score(setup, out["records"], SEED, ("f32", "fp8"))
    program = max(float(g.max()) for g in gaps["f32"])
    control = max(float(g.max()) for g in gaps["fp8"])
    assert program <= LIMITS["max_logit_gap"] < control
    assert control > 3 * program
