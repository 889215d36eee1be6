"""Observability: the metrics registry (counters / gauges / mergeable
percentile histograms, Prometheus export), tracer thread-id mapping and
ring wraparound with GAUGE + SPAN events, the request-lifecycle
Observer + RequestTimeline reconstruction, the flight recorder's
incident dumps and rate limiting, per-request metrics records through
the frontend, and the COMPILED_OUT no-op paths.

Artifact checks reuse the SAME validators the CI observability-smoke
job runs (tools/validate_observability.py), so a test pass here means
the CI gate's grammar checks pass too.
"""
import asyncio
import dataclasses
import importlib.util
import json
import threading
import types
from pathlib import Path

import numpy as np
import pytest

import repro.calculators  # noqa: F401
import repro.core.tracer as trace_mod
from repro.configs import get_config
from repro.core import Graph, parse_graph_config
from repro.core.metrics import (BUCKET_EDGES, MetricsRegistry,
                                NullRegistry)
from repro.core.tracer import NullTracer, Tracer
from repro.serving import (AsyncFrontend, FlightRecorder, GraphServer,
                           LLMEngine, Observer, RequestTimeline,
                           Scheduler, SlotBackend)
from repro.serving.observe import (NULL_OBSERVER, export_run, parse_span,
                                   span_id)

_SPEC = importlib.util.spec_from_file_location(
    "validate_observability",
    Path(__file__).resolve().parent.parent / "tools"
    / "validate_observability.py")
vo = importlib.util.module_from_spec(_SPEC)
_SPEC.loader.exec_module(vo)


def ctotal(snap_entry):
    """Sum of a snapshotted counter's values across label sets."""
    return sum(v["value"] for v in snap_entry["values"])


def hcount(snap_entry):
    """Total observation count of a snapshotted histogram."""
    return sum(v["count"] for v in snap_entry["values"])


def small_cfg():
    cfg = get_config("minicpm_2b").reduced()
    return dataclasses.replace(cfg, num_layers=2, d_model=128,
                               vocab_size=512)


@pytest.fixture(scope="module")
def engine():
    return LLMEngine(small_cfg(), max_len=64, seed=7)


# ---------------------------------------------------------------------------
# Metrics registry
# ---------------------------------------------------------------------------

class TestMetricsRegistry:
    def test_counter_labels_and_total(self):
        reg = MetricsRegistry()
        c = reg.counter("serve.requests_finished", "by reason")
        c.inc(reason="length")
        c.inc(reason="length")
        c.inc(5, reason="eos")
        assert c.value(reason="length") == 2
        assert c.value(reason="eos") == 5
        assert c.value(reason="missing") == 0
        assert c.total() == 7

    def test_gauge_last_write_wins(self):
        reg = MetricsRegistry()
        g = reg.gauge("serve.waiting", "")
        g.set(3)
        g.set(1)
        assert g.value() == 1

    def test_histogram_quantiles_from_buckets(self):
        reg = MetricsRegistry()
        h = reg.histogram("lat_ms", "")
        xs = [0.5, 1.0, 2.0, 4.0, 8.0, 16.0, 100.0, 1000.0]
        for x in xs:
            h.observe(x)
        import math
        for q in (0.5, 0.95, 0.99):
            lo, hi = h.quantile_bounds(q)
            # bucket rank convention: smallest x with cum count >= q*n
            rank = max(1, math.ceil(q * len(xs)))
            exact = sorted(xs)[rank - 1]
            assert lo <= exact <= hi, (q, lo, exact, hi)
            # the point estimate is the clamped upper edge
            est = h.quantile(q)
            assert lo <= est <= max(xs)

    def test_histogram_merge_is_lossless(self):
        a, b = MetricsRegistry(), MetricsRegistry()
        rng = np.random.RandomState(0)
        xs = rng.exponential(10.0, size=200)
        for x in xs[:100]:
            a.histogram("lat", "").observe(float(x))
        for x in xs[100:]:
            b.histogram("lat", "").observe(float(x))
        whole = MetricsRegistry()
        for x in xs:
            whole.histogram("lat", "").observe(float(x))
        merged = MetricsRegistry.merged([a, b])
        hm, hw = merged.get("lat"), whole.get("lat")
        assert hm.quantile_bounds(0.5) == hw.quantile_bounds(0.5)
        assert hm.quantile_bounds(0.95) == hw.quantile_bounds(0.95)
        assert hm.total_count() == hw.total_count() == 200

    def test_merged_skips_null_and_none(self):
        reg = MetricsRegistry()
        reg.counter("c", "").inc()
        merged = MetricsRegistry.merged([None, NullRegistry(), reg])
        assert merged.get("c").total() == 1
        assert merged.names() == ["c"]

    def test_prometheus_text_parses(self, tmp_path):
        reg = MetricsRegistry()
        reg.counter("serve.requests_finished", "by reason").inc(
            reason="length")
        reg.gauge("serve.waiting", "queue depth").set(2)
        h = reg.histogram("serve.ttft_ms", "ttft")
        for x in (0.7, 3.0, 250.0):
            h.observe(x)
        text = reg.to_prometheus()
        p = tmp_path / "m.prom"
        p.write_text(text)
        assert vo.validate_prometheus(p) == []
        # dots sanitize to underscores for Prometheus
        assert "serve_ttft_ms_bucket" in text
        assert 'le="+Inf"' in text

    def test_snapshot_json_round_trips(self):
        reg = MetricsRegistry()
        reg.histogram("h", "").observe(1.0)
        doc = json.loads(reg.snapshot_json())
        assert hcount(doc["h"]) == 1

    def test_null_registry_is_noop(self):
        reg = NullRegistry()
        assert reg.enabled is False
        reg.counter("c", "").inc()
        reg.gauge("g", "").set(1)
        reg.histogram("h", "").observe(1.0)
        assert reg.counter("c", "").value() == 0
        assert reg.histogram("h", "").quantile(0.5) is None
        assert reg.snapshot() == {}
        assert reg.to_prometheus() == ""

    def test_bucket_edges_shared_and_sorted(self):
        assert list(BUCKET_EDGES) == sorted(BUCKET_EDGES)
        assert BUCKET_EDGES[-1] == float("inf")


# ---------------------------------------------------------------------------
# Tracer: thread ids, ring wraparound, trace-file round-trip
# ---------------------------------------------------------------------------

class TestTracer:
    def test_thread_ids_are_compact_and_stable(self, tmp_path):
        t = Tracer(capacity=256)
        barrier = threading.Barrier(4)

        def work(i):
            barrier.wait()
            for j in range(5):
                t.record(trace_mod.PACKET_EMIT, node_id=i,
                         stream_id=f"s{i}", packet_timestamp=j)

        threads = [threading.Thread(target=work, args=(i,))
                   for i in range(3)]
        t.record(trace_mod.OPEN)     # main thread claims an id too
        for th in threads:
            th.start()
        barrier.wait()
        for th in threads:
            th.join()
        tids = {e.thread_id for e in t.events()}
        assert len(tids) == 4                      # main + 3 workers
        assert tids <= set(range(4))               # compact small ids
        # per-thread events share one id
        by_node = {}
        for e in t.events():
            if e.event_type == trace_mod.PACKET_EMIT:
                by_node.setdefault(e.node_id, set()).add(e.thread_id)
        assert all(len(s) == 1 for s in by_node.values())
        out = tmp_path / "trace.json"
        t.export_chrome_trace(str(out))
        doc = json.loads(out.read_text())
        meta = {e["args"]["name"] for e in doc["traceEvents"]
                if e["ph"] == "M" and e["name"] == "thread_name"}
        assert meta == {f"thread-{tid}" for tid in tids}

    def test_ring_wraparound_round_trip(self, tmp_path):
        cap = 32
        t = Tracer(capacity=cap)
        n = 3 * cap + 5
        for i in range(n):
            if i % 3 == 0:
                t.record(trace_mod.GAUGE, stream_id="pool.in_use",
                         packet_data_id=i)
            elif i % 3 == 1:
                t.record(trace_mod.SPAN, node_id=2,
                         stream_id=span_id("token", f"req{i % 4}"),
                         packet_timestamp=i)
            else:
                t.record(trace_mod.RUN_START, node_id=1,
                         packet_timestamp=i)
        evs = t.events()
        assert len(evs) == cap                      # only the last window
        # the ring kept exactly the newest events, oldest first
        seqs = [max(e.packet_timestamp, e.packet_data_id) for e in evs]
        assert seqs == sorted(seqs)
        assert seqs[-1] == n - 1
        assert min(seqs) == n - cap
        # export of a wrapped ring stays loadable, with all three kinds
        out = tmp_path / "wrapped.json"
        t.export_chrome_trace(str(out), node_names={1: "engine"})
        assert vo.validate_trace(out) == [f"{out.name}: no X run slices"]
        doc = json.loads(out.read_text())
        phs = {e["ph"] for e in doc["traceEvents"]}
        assert {"C", "i", "M"} <= phs               # GAUGE + SPAN + meta
        # save/load round-trips the wrapped window exactly
        tf = tmp_path / "trace.jsonl"
        t.save(str(tf), node_names={1: "engine"})
        t2, names = Tracer.load(str(tf))
        assert names == {1: "engine"}
        assert t2.events() == evs
        spans = [e for e in t2.events()
                 if e.event_type == trace_mod.SPAN]
        assert spans and all(parse_span(e.stream_id)[0] == "token"
                             for e in spans)

    def test_null_tracer_noop(self, tmp_path):
        t = NullTracer()
        t.record(trace_mod.SPAN, stream_id=span_id("submitted", "r"))
        assert t.events() == []
        out = tmp_path / "null.json"
        t.export_chrome_trace(str(out))
        assert json.loads(out.read_text())["traceEvents"] == []

    def test_compiled_out_swaps_everything(self):
        saved = trace_mod.COMPILED_OUT
        trace_mod.COMPILED_OUT = True
        try:
            g = Graph(parse_graph_config("""
input_stream: "frame"
output_stream: "out"
enable_tracer: true
node {
  calculator: "PassThroughCalculator"
  input_stream: "IN:frame"
  output_stream: "OUT:out"
}
"""))
            assert isinstance(g.tracer, NullTracer)
            g.start_run()
            g.add_packet_to_input_stream("frame", 1, 0)
            g.close_all_input_streams()
            g.wait_until_done(timeout=30)
            assert g.tracer.events() == []
            eng = LLMEngine(small_cfg(), max_len=32, seed=0)
            assert isinstance(eng.metrics, NullRegistry)
        finally:
            trace_mod.COMPILED_OUT = saved

    def test_null_observer_singleton_is_inert(self):
        assert NULL_OBSERVER.enabled is False
        assert isinstance(NULL_OBSERVER.tracer, NullTracer)
        assert isinstance(NULL_OBSERVER.registry, NullRegistry)
        req = types.SimpleNamespace(id="r", prompt=np.zeros(3, np.int32),
                                    priority=0, preemptions=0, slot=0,
                                    tokens=[], ingested=0)
        NULL_OBSERVER.submitted(req, 1)
        NULL_OBSERVER.finished(req, "length")
        assert NULL_OBSERVER.tracer.events() == []
        assert NULL_OBSERVER.recorder is None       # never mutated


# ---------------------------------------------------------------------------
# Observer spans -> RequestTimeline
# ---------------------------------------------------------------------------

def _fake_req(rid, prompt_len=8, slot=0):
    return types.SimpleNamespace(
        id=rid, prompt=np.zeros(prompt_len, np.int32), priority=0,
        preemptions=0, slot=slot, tokens=[], ingested=0)


class TestRequestTimeline:
    @pytest.fixture()
    def traced_lifecycle(self):
        tracer = Tracer(capacity=1024)
        obs = Observer(tracer=tracer, node_id=3)
        r = _fake_req("reqA")
        obs.submitted(r, waiting=1)
        obs.admitted(r, wait_ms=1.5)
        obs.chunk(r, 0, 8, dur_ms=2.0)
        obs.first_token(r, ttft_ms=5.0, index=0)
        obs.token(r, index=1, itl_ms=1.0)
        obs.verified(r, accepted=2, drafted=3, seq=4)
        obs.preempted(r)
        r.preemptions = 1
        obs.admitted(r, wait_ms=None)               # readmission
        obs.replayed(r, 4)
        obs.token(r, index=2, itl_ms=3.0)
        obs.finished(r, "length")
        # a second request that dies of cache pressure pre-token
        r2 = _fake_req("reqB")
        obs.submitted(r2, waiting=0)
        obs.pressure(r2)
        obs.finished(r2, "cancelled")
        return tracer, obs

    def test_records_reconstruct_lifecycle(self, traced_lifecycle):
        tracer, _ = traced_lifecycle
        recs = {r["id"]: r
                for r in RequestTimeline.from_tracer(tracer).records()}
        a = recs["reqA"]
        assert a["finish_reason"] == "length"
        assert a["tokens"] == 3
        assert a["chunks"] == 1
        assert a["verify_ticks"] == 1 and a["accepted_total"] == 2
        assert a["preemptions"] == 1
        assert a["replayed_tokens"] == 4
        assert a["submitted_ms"] <= a["admitted_ms"] \
            <= a["first_token_ms"] <= a["finished_ms"]
        assert a["queue_wait_ms"] >= 0 and a["ttft_ms"] >= 0
        b = recs["reqB"]
        assert b["finish_reason"] == "cancelled"
        assert b["pressure_events"] == 1
        assert b["first_token_ms"] is None

    def test_readmission_skips_queue_histogram(self, traced_lifecycle):
        _, obs = traced_lifecycle
        h = obs.registry.get("serve.queue_wait_ms")
        assert h.total_count() == 1                 # only first admission

    def test_exports_validate(self, traced_lifecycle, tmp_path):
        tracer, _ = traced_lifecycle
        tl = RequestTimeline.from_tracer(tracer)
        pf = tmp_path / "requests.perfetto.json"
        tj = tmp_path / "timelines.json"
        tl.export_perfetto(str(pf))
        tl.to_json(str(tj))
        assert vo.validate_perfetto_requests(pf) == []
        assert vo.validate_timelines(tj) == []
        doc = json.loads(pf.read_text())
        names = {e["args"]["name"] for e in doc["traceEvents"]
                 if e.get("ph") == "M" and e["name"] == "thread_name"}
        assert names == {"req reqA", "req reqB"}
        segs = [e["name"] for e in doc["traceEvents"]
                if e.get("ph") == "X"]
        assert {"queued", "prefill", "decode", "requeued"} <= set(segs)

    def test_aggregates_land_in_registry(self, traced_lifecycle):
        _, obs = traced_lifecycle
        reg = obs.registry
        assert reg.get("serve.requests_submitted").total() == 2
        assert reg.get("serve.tokens_emitted").total() == 3
        assert reg.get("serve.preemptions").total() == 1
        assert reg.get("serve.replayed_tokens").total() == 4
        assert reg.get("serve.cache_pressure").total() == 1
        assert reg.get("serve.requests_finished").value(
            reason="length") == 1
        assert reg.get("serve.ttft_ms").total_count() == 1
        assert reg.get("serve.spec_accepted_per_tick").total_count() == 1

    def test_span_id_round_trip(self):
        assert parse_span(span_id("first_token", "req@9")) == \
            ("first_token", "req@9")
        assert parse_span("nodelimiter") == ("nodelimiter", "")


# ---------------------------------------------------------------------------
# Flight recorder
# ---------------------------------------------------------------------------

class TestFlightRecorder:
    def _recorder(self, tmp_path, **kw):
        tracer = Tracer(capacity=64)
        for i in range(10):
            tracer.record(trace_mod.SPAN, stream_id=span_id("token", "r"),
                          packet_timestamp=i)
        reg = MetricsRegistry()
        rec = FlightRecorder(str(tmp_path), registry=reg, **kw)
        rec.bind(events_fn=tracer.events,
                 metrics_fn=lambda: {"serve.tokens": {"total": 10}},
                 state_fn=lambda: {"slots": ["r"], "waiting": []})
        return rec, reg

    def test_incident_dump_contents(self, tmp_path):
        rec, reg = self._recorder(tmp_path, last_n=4)
        path = rec.incident("preemption", "request 'r' evicted")
        assert path is not None
        doc = json.loads(Path(path).read_text())
        assert doc["trigger"] == "preemption"
        assert doc["detail"] == "request 'r' evicted"
        assert len(doc["events"]) == 4              # last-N window
        assert doc["events"][-1][4] == 9            # newest span seq
        assert doc["metrics"]["serve.tokens"]["total"] == 10
        assert doc["scheduler"]["slots"] == ["r"]
        assert doc["provenance"]["python"]
        assert Path(path).parent == Path(rec.incident_dir)
        assert reg.get("observe.flight_dumps").total() == 1

    def test_rate_limiting(self, tmp_path):
        rec, reg = self._recorder(tmp_path, max_dumps=3,
                                  min_interval_s=3600.0)
        assert rec.incident("preemption") is not None
        # same trigger inside the interval: suppressed, counted
        assert rec.incident("preemption") is None
        # a different trigger has its own interval clock
        assert rec.incident("cache_pressure") is not None
        assert rec.incident("deadline_miss") is not None
        # global cap reached
        assert rec.incident("executor_error") is None
        assert reg.get("observe.flight_dumps").total() == 3
        assert reg.get("observe.flight_dumps_suppressed").total() == 2
        files = sorted(Path(rec.incident_dir).glob("incident-*.json"))
        assert len(files) == 3

    def test_write_failure_never_raises(self, tmp_path):
        target = tmp_path / "blocked"
        target.write_text("a file where the directory should go")
        rec = FlightRecorder(str(target))
        assert rec.incident("preemption") is None   # swallowed


# ---------------------------------------------------------------------------
# End-to-end: traced GraphServer run
# ---------------------------------------------------------------------------

@pytest.fixture(scope="module")
def traced_run(engine, tmp_path_factory):
    """One traced serve with chunked prefill + speculation; the artifact
    set is reused by every assertion below."""
    out = tmp_path_factory.mktemp("obs")
    rng = np.random.RandomState(3)
    prompts = [rng.randint(0, 512, size=L).astype(np.int32)
               for L in (11, 11, 7)]
    with GraphServer(engine, num_slots=2, max_new_tokens=5,
                     chunk_size=8, speculate_k=3,
                     observe_dir=str(out)) as srv:
        handles = [srv.submit(p, request_id=f"req-{i}")
                   for i, p in enumerate(prompts)]
        results = [h.result(timeout=600) for h in handles]
        arts = srv.dump_observability()
        snap = srv.metrics()
        text = srv.metrics_text()
        per_req = [h.metrics for h in handles]
    return types.SimpleNamespace(out=out, arts=arts, snap=snap,
                                 text=text, per_req=per_req,
                                 results=results, prompts=prompts)


class TestServerIntegration:
    def test_artifact_set_validates(self, traced_run):
        assert set(traced_run.arts) == {
            "trace.json", "requests.perfetto.json", "timelines.json",
            "metrics.json", "metrics.prom", "provenance.json"}
        assert vo.validate_dir(traced_run.out) == []

    def test_timelines_cover_every_request(self, traced_run):
        doc = json.loads((traced_run.out / "timelines.json").read_text())
        recs = {r["id"]: r for r in doc["requests"]}
        assert set(recs) == {"req-0", "req-1", "req-2"}
        for i, r in enumerate(traced_run.results):
            rec = recs[f"req-{i}"]
            assert rec["finish_reason"] == "length"
            assert rec["tokens"] == len(r) == 5
            assert rec["ttft_ms"] >= rec["queue_wait_ms"] >= 0

    def test_metrics_snapshot_names(self, traced_run):
        names = set(traced_run.snap)
        assert {"serve.ttft_ms", "serve.itl_ms", "serve.queue_wait_ms",
                "serve.decode_step_ms", "serve.batch_occupancy",
                "serve.requests_submitted", "serve.requests_finished",
                "serve.tokens_emitted", "engine.compiles",
                "engine.compile_ms"} <= names
        assert ctotal(traced_run.snap["serve.requests_finished"]) == 3
        assert ctotal(traced_run.snap["serve.tokens_emitted"]) == 15
        assert hcount(traced_run.snap["serve.ttft_ms"]) == 3

    def test_engine_jit_labels(self, engine):
        reg = engine.metrics
        c = reg.get("engine.compiles")
        assert c.total() >= 2                       # prefill + decode
        assert c.value(fun="jit(slot_decode_step)") >= 1
        hist = reg.get("engine.compile_ms")
        assert hist.quantile(0.5, fun="jit(slot_decode_step)") is not None

    def test_paged_decode_pages_counter(self):
        """A paged decode step is labelled path="paged", and
        ``engine.decode_pages`` adds the active rows' live pages (read)
        and rows x table width (table): two rows of 5 and 12 prompt
        tokens, 8-token pages, 4-page tables, five decode steps at
        positions 5..9 and 12..16."""
        from repro.serving import PagedBackend
        eng = LLMEngine(small_cfg(), max_len=32, seed=7)
        sched = Scheduler(PagedBackend(eng, 2, num_blocks=9, block_size=8),
                          max_new_tokens=6)
        rng = np.random.RandomState(8)
        for i, n in enumerate((5, 12)):
            sched.submit({"tokens": rng.randint(0, 512, size=n).astype(
                np.int32), "id": i})
        while sched.has_work():
            sched.admit()
            sched.step()
        reg = eng.metrics
        assert reg.get("engine.kernel_path").value(
            path="paged", step="decode", layout="paged/8") == 5
        pages = reg.get("engine.decode_pages")
        # row 0: 1+1+1+2+2 live pages; row 1: 2+2+2+2+3
        assert pages.value(kind="read") == 7 + 11
        assert pages.value(kind="table") == 5 * 2 * 4

    def test_prometheus_export_validates(self, traced_run, tmp_path):
        p = tmp_path / "server.prom"
        p.write_text(traced_run.text)
        assert vo.validate_prometheus(p) == []
        assert "serve_ttft_ms_bucket" in traced_run.text

    def test_per_request_metrics_on_handle(self, traced_run):
        for i, m in enumerate(traced_run.per_req):
            assert m is not None
            assert m["id"] == f"req-{i}"
            assert m["finish_reason"] == "length"
            assert m["tokens"] == 5
            assert m["ttft_ms"] >= 0
            assert m["queue_wait_ms"] >= 0
            assert m["spec_drafted"] >= m["spec_accepted"] >= 0

    def test_observability_does_not_change_tokens(self, traced_run,
                                                  engine):
        for p, r in zip(traced_run.prompts, traced_run.results):
            ref = engine.generate(p[None], max_new_tokens=5)[0]
            assert np.array_equal(ref, r)

    def test_preemption_fires_flight_recorder(self, engine,
                                              tmp_path_factory):
        out = tmp_path_factory.mktemp("incidents")
        rng = np.random.RandomState(4)
        n = 6
        prompts = [rng.randint(0, 512, size=6).astype(np.int32)
                   for _ in range(n)]
        with GraphServer(engine, num_slots=n, max_new_tokens=4,
                         paged=True, block_size=8, num_blocks=6,
                         admission="preempt",
                         observe_dir=str(out)) as srv:
            handles = [srv.submit(p) for p in prompts]
            for h in handles:
                h.result(timeout=600)
            stats = srv.stats()
            snap = srv.metrics()
        assert stats["scheduler"]["preemptions"] > 0
        files = sorted((out / "incidents").glob("incident-*.json"))
        assert files, "no flight-recorder dump for preemption"
        doc = json.loads(files[0].read_text())
        assert doc["trigger"] in FlightRecorder.TRIGGERS
        assert doc["events"], "incident dump lost the trace window"
        assert doc["scheduler"]["slots"] is not None
        assert ctotal(snap["observe.flight_dumps"]) >= 1
        assert ctotal(snap["serve.preemptions"]) == \
            stats["scheduler"]["preemptions"]


# ---------------------------------------------------------------------------
# Frontend per-request metrics record
# ---------------------------------------------------------------------------

class TestFrontendMetrics:
    def test_on_metrics_record(self, engine):
        with GraphServer(engine, num_slots=2, max_new_tokens=4) as srv:
            front = AsyncFrontend(srv)
            got = []

            async def run():
                toks = await front.generate(
                    np.arange(1, 7, dtype=np.int32),
                    request_id="fm-0", on_metrics=got.append)
                return toks

            toks = asyncio.run(run())
            agg = front.metrics()
        assert len(got) == 1
        m = got[0]
        assert m["id"] == "fm-0"
        assert m["finish_reason"] == "length"
        assert m["tokens"] == len(toks) == 4
        assert m["ttft_ms"] > 0
        assert m["itl_ms"] is not None \
            and m["itl_ms"]["p50"] <= m["itl_ms"]["max"]
        sched = m["scheduler"]
        assert sched["id"] == "fm-0"
        assert sched["ttft_ms"] >= 0 and sched["queue_wait_ms"] >= 0
        # client-side TTFT includes the dispatcher hop: never smaller
        assert m["ttft_ms"] >= sched["ttft_ms"] - 1.0
        assert ctotal(agg["serve.requests_finished"]) == 1


# ---------------------------------------------------------------------------
# export_run on a bare tracer (no server)
# ---------------------------------------------------------------------------

class TestExportRun:
    def test_export_run_writes_full_set(self, tmp_path):
        tracer = Tracer(capacity=256)
        obs = Observer(tracer=tracer, node_id=0)
        r = _fake_req("x")
        obs.submitted(r, 0)
        obs.admitted(r, 0.5)
        obs.first_token(r, 2.0)
        obs.finished(r, "eos")
        tracer.record(trace_mod.RUN_START, node_id=0, packet_timestamp=1)
        tracer.record(trace_mod.RUN_END, node_id=0, packet_timestamp=1)
        arts = export_run(str(tmp_path), tracer=tracer,
                          node_names={0: "engine"},
                          registry=obs.registry, argv=["test"])
        assert vo.validate_dir(tmp_path) == []
        prov = json.loads((tmp_path / "provenance.json").read_text())
        assert prov["argv"] == ["test"]
        assert set(arts) == {
            "trace.json", "requests.perfetto.json", "timelines.json",
            "metrics.json", "metrics.prom", "provenance.json"}
