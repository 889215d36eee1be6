"""The profiler-trace reduction on a small recorded trace: busy time and
idle share, per-program device time, how calls are priced from their
host annotations, and the least-time roofline built on them."""
import json
import types
from pathlib import Path

import pytest

from bench import readers, shapes, trace

DATA = Path(__file__).parent / "data"


@pytest.fixture(scope="module")
def small():
    t = json.loads((DATA / "small_trace.json").read_text())
    return trace.reduce(t, t["window_s"])


def test_busy_is_the_union_of_op_intervals(small):
    # ops cover [1000,5000) + [6000,7500) + [7600,8000) + [10000,12000) ns
    assert small.busy_s == pytest.approx(7900e-9)
    assert small.window_s == pytest.approx(12e-6)
    assert small.idle_share == pytest.approx(1 - 7900 / 12000)


def test_program_device_time_by_module_name(small):
    assert small.programs == {
        "jit_prefill_step": (1, pytest.approx(4e-6)),
        "jit_paged_decode_step": (2, pytest.approx(4e-6))}
    assert small.program_seconds("decode") == (2, pytest.approx(4e-6))


def test_calls_are_priced_by_the_annotation_around_them(small):
    assert [(p, a) for p, _, a in small.calls] == [
        ("jit_prefill_step", "bench.prefill:100"),
        ("jit_paged_decode_step", "bench.decode:50:2"),
        ("jit_paged_decode_step", "bench.decode:52:2")]
    assert small.priced == 3


def test_idle_gaps_are_named_by_host_activity(small):
    assert small.idle_gaps == [("host", pytest.approx(2000e-9)),
                               ("host", pytest.approx(1000e-9)),
                               ("bench.decode", pytest.approx(100e-9))]


def test_top_ops_carry_their_program(small):
    top = dict(small.top_ops)
    assert top["jit_paged_decode_step:fusion.3"] == pytest.approx(3500e-9)
    assert top["jit_prefill_step:dot.2"] == pytest.approx(2000e-9)


def test_least_time_roofline(small):
    dims = shapes.Dims(layers=2, d=8, heads=2, kv_heads=2, head_dim=4,
                       ffn=16, vocab=32, tied=True)
    peaks = {"bf16_flops_per_s": 1e12, "hbm_bytes_per_s": 1e9}
    run = types.SimpleNamespace(trace=small, dims=dims, peaks=peaks)
    # decode rows: least time is the larger of flops/peak and bytes/bw
    want = 0.0
    for ctx in (50, 52):
        f, b = dims.decode([0, 0])
        f += dims.attention_flops(ctx)
        b += ctx * dims.kv_bytes_per_token
        want += max(f / 1e12, b / 1e9)
    assert readers.roofline_pct(run, "decode") == pytest.approx(
        100 * want / 4e-6)
    f, b = dims.prefill(100)
    assert readers.roofline_pct(run, "prefill") == pytest.approx(
        100 * max(f / 1e12, b / 1e9) / 4e-6)


def test_no_priced_call_reads_nothing(small):
    run = types.SimpleNamespace(trace=None)
    assert readers.roofline_pct(run, "decode") is None


def test_a_trace_without_device_ops_is_refused():
    with pytest.raises(ValueError):
        trace.reduce({"planes": [{"name": "/host:CPU", "lines": []}]}, 1.0)
