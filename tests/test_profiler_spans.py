"""The program's spans and scopes on the profiler's clock: the tick-phase
span tree of a paged GraphServer (nesting and threads), nothing of it
under COMPILED_OUT with the same tokens, the process-wide compile counter
counting a retrace, the layer-kind scopes in the compiled decode step's
op metadata, and the trace ring's profiler-clock offset."""
import dataclasses
import glob
import os
import re
import time

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.profiler import ProfileData, TraceAnnotation

import repro.core.tracer as trace_mod
from repro.configs import get_config
from repro.core.tracer import Tracer
from repro.serving import GraphServer, LLMEngine
from repro.serving.kvcache.backend import make_backend

OURS = ("serve.", "engine.", "graph.")


def small_cfg():
    cfg = get_config("minicpm_2b").reduced()
    return dataclasses.replace(cfg, num_layers=2, d_model=128,
                               vocab_size=512)


def prompts():
    rng = np.random.RandomState(3)
    return [rng.randint(0, 512, size=n).astype(np.int32)
            for n in (7, 7, 11)]


def profile(fn, log_dir):
    """Run ``fn`` under a profiler trace; returns (its result, the
    trace's host lines as [[(name, start, end, args), ...], ...])."""
    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    jax.profiler.start_trace(str(log_dir), profiler_options=opts)
    try:
        out = fn()
    finally:
        jax.profiler.stop_trace()
    path = sorted(glob.glob(os.path.join(str(log_dir), "plugins",
                                         "profile", "*", "*.xplane.pb")))[-1]
    lines = []
    for plane in ProfileData.from_file(path).planes:
        if plane.name.startswith("/host"):
            for line in plane.lines:
                lines.append([(e.name, e.start_ns, e.end_ns, dict(e.stats))
                              for e in line.events])
    return out, lines


def serve(engine, log_dir):
    """Warm a two-slot paged server up, then serve three prompts (two
    lengths) under the profiler."""
    with GraphServer(engine, num_slots=2, max_new_tokens=4,
                     backend="paged", block_size=8) as srv:
        for p in prompts()[1:]:
            srv.submit(p, max_new_tokens=2).result(timeout=600)

        def run():
            handles = [srv.submit(p) for p in prompts()]
            return [h.result(timeout=600) for h in handles]

        return profile(run, log_dir)


@pytest.fixture(scope="module")
def traced(tmp_path_factory):
    engine = LLMEngine(small_cfg(), max_len=64, seed=7)
    tokens, lines = serve(engine, tmp_path_factory.mktemp("prof"))
    return engine, tokens, lines


def spans(lines, name):
    return [(i, s) for i, line in enumerate(lines) for s in line
            if s[0] == name]


def parent(lines, i, span, names):
    """The innermost span of ``names`` on the same thread around
    ``span``."""
    around = [s for s in lines[i] if s[0] in names and s is not span
              and s[1] <= span[1] and span[2] <= s[2]]
    return max(around, key=lambda s: s[1]) if around else None


NESTING = {
    "serve.admit": ("serve.tick",),
    "serve.prefill": ("serve.admit",),
    "engine.prefill": ("serve.prefill",),
    "engine.insert": ("serve.prefill",),
    "serve.grow": ("serve.tick",),
    "serve.decode": ("serve.tick",),
    "engine.decode": ("serve.decode",),
    "engine.decode.inputs": ("engine.decode",),
    "engine.decode.sync": ("engine.decode",),
    "serve.emit": ("serve.tick",),
    "serve.tick": ("graph.run",),
}


def test_span_tree_nests_on_the_engine_thread(traced):
    _, _, lines = traced
    for child, parents in NESTING.items():
        found = spans(lines, child)
        assert found, child
        for i, s in found:
            p = parent(lines, i, s, parents)
            assert p is not None, (child, s)
    ticks = spans(lines, "serve.tick")
    assert len({i for i, _ in ticks}) == 1            # one engine thread
    engine_line = ticks[0][0]
    for i, s in ticks:
        assert parent(lines, i, s, ("graph.run",))[3] == {"node": "engine"}
        assert isinstance(s[3]["step"], int)
    # every prefill length was served, each under its own spans
    assert sorted({s[3]["tokens"] for _, s in
                   spans(lines, "engine.prefill")}) == [7, 11]
    # the token pump delivers on a thread of its own
    deliver = spans(lines, "serve.deliver")
    assert deliver and all(i != engine_line for i, _ in deliver)
    assert sum(s[3]["tokens"] for _, s in deliver) == 3 * 4


def test_decode_and_prefill_spans_carry_the_call_shapes(traced):
    _, _, lines = traced
    for i, s in spans(lines, "engine.decode"):
        outer = parent(lines, i, s, ("serve.decode",))
        assert s[3] == outer[3]
        assert 1 <= s[3]["rows"] <= 2 and s[3]["ctx"] >= s[3]["rows"]
    for i, s in spans(lines, "engine.prefill"):
        outer = parent(lines, i, s, ("serve.prefill",))
        assert s[3] == outer[3] == {"tokens": s[3]["tokens"], "rows": 1}


def test_compiled_out_records_no_span_and_serves_the_same_tokens(
        traced, tmp_path):
    _, tokens, _ = traced
    saved = trace_mod.COMPILED_OUT
    trace_mod.COMPILED_OUT = True
    try:
        engine = LLMEngine(small_cfg(), max_len=64, seed=7)
        got, lines = serve(engine, tmp_path)
    finally:
        trace_mod.COMPILED_OUT = saved
    assert not [s for line in lines for s in line
                if s[0].startswith(OURS)]
    assert not engine.metrics.enabled
    for a, b in zip(got, tokens):
        np.testing.assert_array_equal(a, b)
    for p, r in zip(prompts(), tokens):
        np.testing.assert_array_equal(
            engine.generate(p[None], max_new_tokens=4)[0], r)


def compiles(engine, fun):
    return engine.metrics.get("engine.compiles").value(fun=fun)


def test_compile_counter_sees_a_retrace():
    engine = LLMEngine(small_cfg(), max_len=64, seed=7)
    fun = "jit(prefill_step)"
    before = compiles(engine, fun)
    engine.prefill(np.zeros((1, 5), np.int32))
    engine.prefill(np.zeros((1, 5), np.int32))
    assert compiles(engine, fun) == before + 1
    # a second prompt length retraces the same jitted step: the old
    # first-call wrapper counted one compile per jit, this counts two
    engine.prefill(np.zeros((1, 9), np.int32))
    assert compiles(engine, fun) == before + 2
    hist = engine.metrics.get("engine.compile_ms")
    assert hist.count(fun=fun) == compiles(engine, fun)
    # one process-wide instrument, shared by every engine's registry
    other = LLMEngine(small_cfg(), max_len=64, seed=8)
    assert other.metrics.get("engine.compiles") is \
        engine.metrics.get("engine.compiles")


def test_decode_hlo_carries_layer_kind_scopes():
    engine = LLMEngine(small_cfg(), max_len=64, seed=7)
    backend = make_backend(engine, backend="paged", num_slots=2,
                           num_blocks=17, block_size=8)
    step = engine._serve_steps(backend)["decode"]
    n = backend.num_slots
    text = step.lower(engine.params, jnp.zeros((n, 1), jnp.int32),
                      engine.new_cache(backend), jnp.zeros(n, jnp.int32),
                      jnp.ones(n, bool),
                      jnp.zeros((n, 8), jnp.int32)).compile().as_text()
    # the per-platform dispatch of paged decode attention adds
    # ``cond/branch_<n>_fun`` components, which are no scope
    paths = {re.sub(r"cond/branch_\d+_fun/", "", p)
             for p in re.findall(r'op_name="([^"]+)"', text)}
    for scope in ("attn/gather/", "attn/kv_write/", "/ffn/", "/cache/",
                  "/norm/", "/embed/", "/head/"):
        assert any(scope in p for p in paths), scope


def test_ring_events_convert_onto_the_profiler_clock(tmp_path):
    tracer = Tracer(64)

    def back_to_back():
        tracer.record(trace_mod.RUN_START, 0)
        with TraceAnnotation("ring.probe"):
            pass

    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    jax.profiler.start_trace(str(tmp_path), profiler_options=opts)
    back_to_back()
    time.sleep(0.01)
    jax.profiler.stop_trace()
    path = glob.glob(os.path.join(str(tmp_path), "plugins", "profile",
                                  "*", "*.xplane.pb"))[0]
    data = ProfileData.from_file(path)
    env = {k: v for p in data.planes for k, v in p.stats}
    probe = next(e for p in data.planes for ln in p.lines
                 for e in ln.events if e.name == "ring.probe")
    ring = tracer.events()[0]
    on_profile = tracer.profiler_ns(ring.event_time) - \
        env["profile_start_time"]
    assert abs(on_profile - probe.start_ns) < 1e6
