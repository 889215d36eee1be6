"""Attribution of a trace to the program's own spans and scopes, on a
small recorded trace (``data/scoped_trace.json``): a decode program whose
scan ``while`` op holds its body ops, the op path of each instruction
(``op_paths``, as a compiled step's metadata gives them), and two ticks
whose phases nest ``serve.tick`` -> ``serve.decode`` -> ``bench.decode``
-> ``engine.decode``; and the program's compile counter as the benchmark
reads it.  Every number below is counted by hand from the file."""
import copy
import json
import types
from pathlib import Path

import pytest

from bench import attribution, spec

DATA = Path(__file__).parent / "data"
DECODE = "jit_paged_decode_step"


@pytest.fixture(scope="module")
def raw():
    return json.loads((DATA / "scoped_trace.json").read_text())


@pytest.fixture(scope="module")
def att(raw):
    return attribution.attribute(raw, raw["window_s"], raw["op_paths"])


def test_self_time_per_scope(att):
    # while.1 [2000,8000) holds fusion.2..5 (2000+1500+1000+500 ns)
    assert att.scopes[DECODE] == {
        "(unscoped)": pytest.approx(1000e-9),
        "attn/gather": pytest.approx(2000e-9),
        "attn": pytest.approx(1500e-9),
        "cache": pytest.approx(1000e-9),
        "ffn": pytest.approx(500e-9),
        "head": pytest.approx(1500e-9)}
    assert att.scopes["jit_prefill_step"] == {"attn": pytest.approx(3e-6)}
    assert att.executions == {DECODE: 1, "jit_prefill_step": 1}
    # the container's own time is the one op that names no scope
    assert att.unscoped == {DECODE: {"while.1": pytest.approx(1000e-9)}}


def test_summary_line_names_scopes_and_unscoped_ops(att):
    line = attribution.summary_line(att, DECODE)
    assert "idle in tick 0.0000s, between ticks 0.0000s" in line
    assert "attn/gather 0.00ms (26.7%)" in line
    assert line.endswith("unscoped while.1 0.00ms")


def test_the_container_is_not_counted_twice(att):
    # self times add up to the union of the program's op intervals
    assert sum(att.scopes[DECODE].values()) == pytest.approx(7500e-9)
    assert att.scope_ms(DECODE, "attn") == pytest.approx(3500e-6)
    assert att.scope_ms(DECODE, "attn/gather") == pytest.approx(2000e-6)
    assert att.scoped_share(DECODE) == pytest.approx(1 - 1000 / 7500)


def test_self_times_of_nested_intervals():
    assert attribution.self_times([(0, 10), (1, 2), (4, 3), (5, 1),
                                   (20, 5)]) == [5, 2, 2, 1, 5]


def test_idle_split_by_tick(att):
    # idle [0,2000) [8000,8500) [10000,13000) [16000,20000); ticks
    # [1500,10500) and [12000,17000)
    assert att.busy_s == pytest.approx(10500e-9)
    assert att.idle_in_tick_s == pytest.approx(3500e-9)
    assert att.idle_between_ticks_s == pytest.approx(6000e-9)
    assert (att.idle_in_tick_s + att.idle_between_ticks_s) / \
        att.window_s == pytest.approx(1 - att.busy_s / att.window_s)


def test_idle_by_innermost_phase(att):
    want = {"(no span)": 5600, "graph.run:engine": 400, "serve.admit": 500,
            "serve.decode": 200, "engine.decode.inputs": 100,
            "engine.decode.sync": 600, "serve.emit": 200, "serve.tick": 600,
            "serve.prefill": 300, "engine.prefill": 1000}
    assert att.phase_idle_s == {k: pytest.approx(v * 1e-9)
                                for k, v in want.items()}


def test_calls_carry_the_step_span_and_the_annotation(att):
    got = [(c.program, c.span, c.args, c.annotation) for c in att.calls]
    assert got == [
        (DECODE, "engine.decode", {"ctx": 50, "rows": 2},
         "bench.decode:50:2"),
        ("jit_prefill_step", "engine.prefill", {"tokens": 100, "rows": 1},
         "bench.prefill:100")]


def test_innermost_pieces():
    pieces = attribution.innermost([(0, 10, "a"), (2, 4, "b"),
                                    (3, 4, "c"), (12, 13, "d")])
    assert pieces == [(0, 2, "a"), (2, 3, "b"), (3, 4, "c"),
                      (4, 10, "a"), (12, 13, "d")]


def test_scope_of_an_op_path():
    path = "jit(f)/while/body/closed_call/attn/kv_write/jit(fd)/sign"
    assert attribution.scope_of(path) == "attn/kv_write"
    assert attribution.scope_of("jit(f)/while") == "(unscoped)"
    assert attribution.scope_of(None) == "(unscoped)"


def test_an_op_event_names_its_instruction():
    assert attribution.hlo_name(
        "%copy.57 = bf16[40,353]{1,0} copy(bf16[40,353]{1,0} %p.1)") \
        == "copy.57"
    assert attribution.hlo_name("fusion.2") == "fusion.2"


HLO = """HloModule jit_paged_decode_step

%fused_computation.5 (param_0: f32[4]) -> f32[4] {
  %param_0 = f32[4]{0} parameter(0)
  %gather.1 = f32[4]{0} gather(%param_0), metadata={op_name="jit(paged_decode_step)/while/body/attn/gather/gather"}
  ROOT %mul.2 = f32[4]{0} multiply(%gather.1, %gather.1), metadata={op_name="jit(paged_decode_step)/while/body/attn/gather/mul"}
}

ENTRY %main.20 (p: f32[4]) -> f32[4] {
  %p = f32[4]{0} parameter(0)
  %fusion.2 = f32[4]{0} fusion(%p), kind=kLoop, calls=%fused_computation.5
  %copy.4 = f32[4]{0} copy(%fusion.2), metadata={op_name="jit(paged_decode_step)/while/body/cache/dynamic_update_slice"}
  ROOT %copy.5 = f32[4]{0} copy(%copy.4)
}
"""


def test_op_paths_from_the_compiled_module(raw):
    paths = attribution.hlo_op_paths(HLO)
    assert paths["fusion.2"].endswith("/attn/gather/mul")
    assert paths["copy.4"].endswith("/cache/dynamic_update_slice")
    assert "copy.5" not in paths
    # the trace's op events are read through the map
    att = attribution.attribute(raw, raw["window_s"],
                                op_paths={DECODE: paths})
    assert att.scopes[DECODE]["attn/gather"] == pytest.approx(2000e-9)
    assert att.scopes[DECODE]["cache"] == pytest.approx(1000e-9)


def test_no_scoped_op_reads_none(raw):
    # no op path names a scope: none given, or none that is a scope
    att = attribution.attribute(raw, raw["window_s"])
    assert att.scopes == {}
    unscoped = {p: {i: "jit(f)/while/body/dot_general" for i in ops}
                for p, ops in raw["op_paths"].items()}
    att = attribution.attribute(raw, raw["window_s"], unscoped)
    assert att.scopes == {}
    assert att.scope_ms(DECODE, "attn") is None
    assert att.scoped_share(DECODE) is None


def test_no_tick_reads_none(raw):
    bare = copy.deepcopy(raw)
    for plane in bare["planes"]:
        for line in plane["lines"]:
            line["events"] = [ev for ev in line["events"]
                              if not ev[0].startswith("serve.")]
    att = attribution.attribute(bare, bare["window_s"], bare["op_paths"])
    assert att.idle_in_tick_s is None and att.idle_between_ticks_s is None
    assert att.scope_ms(DECODE, "attn") == pytest.approx(3500e-6)


def test_a_trace_without_device_ops_is_refused(raw):
    host = {"planes": [p for p in raw["planes"]
                       if not p["name"].startswith("/device")]}
    with pytest.raises(ValueError):
        attribution.attribute(host, raw["window_s"])


def _reg(total):
    if total is None:
        return {"metrics": {}}
    return {"metrics": {"engine.compiles": {
        "type": "counter", "values": [
            {"labels": {"fun": "jit(prefill_step)"}, "value": total - 1},
            {"labels": {"fun": "jit(paged_decode_step)"}, "value": 1}]}}}


@pytest.mark.parametrize("name", ["engine_compiles.rate",
                                  "engine_compiles.backlog"])
def test_engine_compiles_reads_the_gain_in_the_window(name):
    read = spec.load_reader(name)
    assert read(types.SimpleNamespace(reg0=_reg(10), reg1=_reg(12))) == 2
    assert read(types.SimpleNamespace(reg0=_reg(10), reg1=_reg(10))) == 0
    # a program without the counter reads nothing, not 0
    assert read(types.SimpleNamespace(reg0=_reg(None),
                                      reg1=_reg(None))) is None


def test_program_spans_match_the_harness_annotations(tmp_path):
    """A served run of a two-layer cut, traced: every ``engine.decode``
    and ``engine.prefill`` span of the program sits in the harness's
    ``bench.*`` annotation of the same call and names the same shape."""
    import time

    from bench import harness
    from bench.tests.test_bench_correct import SEED, tiny_cell

    t = time.perf_counter()
    setup = harness.prepare(tiny_cell(), SEED, trace=True, t_start=t,
                            require_tpu=False, peaks={},
                            log=lambda *a: None)
    harness.serve(setup, SEED, 1.5, t_start=t, trace_dir=str(tmp_path),
                  log=lambda *a: None)
    planes = attribution.load_xplane_stats(str(tmp_path))["planes"]
    seen = {"engine.prefill": 0, "engine.decode": 0}
    for plane in planes:
        for line in plane["lines"]:
            bench = [ev for ev in line["events"]
                     if ev[0].startswith("bench.")]
            for name, start, dur, args in line["events"]:
                if name not in seen:
                    continue
                outer = [b[0] for b in bench if b[1] <= start
                         and start + dur <= b[1] + b[2]]
                want = (f"bench.prefill:{args['tokens']}"
                        if name == "engine.prefill" else
                        f"bench.decode:{args['ctx']}:{args['rows']}")
                assert outer == [want]
                seen[name] += 1
    assert seen["engine.prefill"] > 0 and seen["engine.decode"] > 0
