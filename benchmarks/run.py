"""Benchmark harness — one function per paper claim/table.

The paper (a framework paper) is evaluated on framework properties, not
task accuracy; each bench validates one §4-§6 claim:

  scheduler_pipelining   — decentralized scheduling raises throughput with
                           more executor threads (§4.1.2)
  sync_policy_overhead   — the default deterministic join vs the immediate
                           policy (§4.1.3)
  flow_limiter           — bounded in-flight work + upstream drops under
                           overload (§4.1.4, Fig. 3)
  tracer_overhead        — tracing is cheap and can be compiled out (§5.1)
  detection_pipeline     — Fig.-1 graph end-to-end FPS (§6.1)
  llm_serving            — flow-limited LLM serving graph tok/s (§6 adapted)
  kernels                — Pallas flash-attn / rmsnorm vs jnp oracle (us)

Output: ``name,us_per_call,derived`` CSV lines (+ a human summary).
"""
from __future__ import annotations

import dataclasses
import time
from typing import List

import numpy as np

ROWS: List[str] = []


def emit(name: str, us_per_call: float, derived: str = "") -> None:
    ROWS.append(f"{name},{us_per_call:.1f},{derived}")
    print(f"{name},{us_per_call:.1f},{derived}")


# ---------------------------------------------------------------------------

def _chain_graph(n_nodes: int, threads: int, delay: float,
                 tracer: bool = False):
    import repro.calculators  # noqa: F401
    from repro.core import GraphBuilder
    from repro.core import register_calculator, Calculator, contract, AnyType

    if not hasattr(_chain_graph, "_registered"):
        @register_calculator(name="BenchSpinCalculator")
        class BenchSpinCalculator(Calculator):
            CONTRACT = (contract().add_input("IN", AnyType)
                        .add_output("OUT"))

            def open(self, ctx):
                self.delay = float(ctx.options.get("delay", 0.0))

            def process(self, ctx):
                p = ctx.inputs["IN"]
                if p.is_empty():
                    return
                if self.delay:
                    # sleep models a device-bound stage (GIL released, as
                    # with real accelerator dispatch)
                    time.sleep(self.delay)
                ctx.outputs("OUT").add_packet(p)

        _chain_graph._registered = True

    b = GraphBuilder(num_threads=threads, enable_tracer=tracer)
    s = b.input("s0")
    for i in range(n_nodes):
        node = b.add_node("BenchSpinCalculator", name=f"n{i}",
                          inputs={"IN": s}, options={"delay": delay})
        s = node.out("OUT", name=f"s{i+1}")
    b.output(s)
    return b.build()


def _run_chain(cfg, n_packets: int, out_stream: str) -> float:
    from repro.core import Graph
    g = Graph(cfg)
    done = []
    g.observe_output_stream(out_stream, lambda p: done.append(p))
    g.start_run()
    t0 = time.perf_counter()
    for t in range(n_packets):
        g.add_packet_to_input_stream("s0", t, t)
    g.close_all_input_streams()
    g.wait_until_done(timeout=120)
    dt = time.perf_counter() - t0
    assert len(done) == n_packets
    return dt


def bench_scheduler_pipelining() -> None:
    """Claim §4.1.2: nodes process different timestamps concurrently, so a
    4-stage pipeline of 1ms stages approaches 1ms/packet with >=4 threads
    rather than 4ms/packet."""
    n, stages, delay = 100, 4, 0.001
    t1 = _run_chain(_chain_graph(stages, 1, delay), n, f"s{stages}")
    t4 = _run_chain(_chain_graph(stages, 6, delay), n, f"s{stages}")
    emit("scheduler_serial_1thread", t1 / n * 1e6,
         f"{n/t1:.0f} pkt/s")
    emit("scheduler_pipelined_6threads", t4 / n * 1e6,
         f"{n/t4:.0f} pkt/s; speedup x{t1/t4:.2f}")


def bench_sync_policy_overhead() -> None:
    """§4.1.3: cost of the deterministic default join vs a plain chain."""
    import repro.calculators  # noqa: F401
    from repro.core import Graph, GraphBuilder
    n = 2000
    # plain 2-node chain
    t_chain = _run_chain(_chain_graph(2, 4, 0.0), n, "s2")
    # fan-out/join with the default policy
    b = GraphBuilder(num_threads=4)
    s0 = b.input("s0")
    left = b.add_node("BenchSpinCalculator", name="a", inputs={"IN": s0})
    right = b.add_node("BenchSpinCalculator", name="b", inputs={"IN": s0})
    join = b.add_node("PassThroughCalculator", name="join",
                      inputs={"l": left.out("OUT", name="l"),
                              "r": right.out("OUT", name="r")})
    b.output(join.out("l", name="out"))
    g = Graph(b.build())
    done = []
    g.observe_output_stream("out", lambda p: done.append(p))
    g.start_run()
    t0 = time.perf_counter()
    for t in range(n):
        g.add_packet_to_input_stream("s0", t, t)
    g.close_all_input_streams()
    g.wait_until_done(timeout=120)
    t_join = time.perf_counter() - t0
    emit("sync_chain_per_packet", t_chain / n * 1e6, "")
    emit("sync_default_join_per_packet", t_join / n * 1e6,
         f"overhead x{t_join/t_chain:.2f}")


def bench_flow_limiter() -> None:
    """§4.1.4: under 4x overload the limiter keeps end-to-end latency of
    ADMITTED packets near the no-load service time and drops the rest
    upstream."""
    import repro.calculators  # noqa: F401
    from repro.core import Graph, GraphBuilder
    service = 0.004
    b = GraphBuilder(num_threads=4)
    incoming = b.input("in")
    finished = b.loopback()
    lim = b.add_node("FlowLimiterCalculator", name="lim",
                     inputs={"IN": incoming, "FINISHED": finished},
                     options={"max_in_flight": 1})
    work = b.add_node("BenchSpinCalculator", name="work",
                      inputs={"IN": lim.out("OUT", name="adm")},
                      options={"delay": service})
    out = b.output(work.out("OUT", name="out"))
    loop = b.add_node("PassThroughCalculator", name="loop",
                      inputs={"out": out})
    finished.tie(loop.out("out", name="loop"))
    g = Graph(b.build())
    lat = {}
    sub = {}
    g.observe_output_stream("out", lambda p: lat.__setitem__(
        p.timestamp.value, time.perf_counter() - sub[p.timestamp.value]))
    g.start_run()
    n = 150
    for t in range(n):
        sub[t] = time.perf_counter()
        g.add_packet_to_input_stream("in", t, t)
        time.sleep(service / 4)          # 4x overload
    g.close_all_input_streams()
    g.wait_until_done(timeout=120)
    lim = next(nd for nd in g.nodes if nd.name == "lim").calculator
    p95 = sorted(lat.values())[int(len(lat) * 0.95)]
    emit("flow_limiter_admitted_p95", p95 * 1e6,
         f"admitted={lim.admitted} dropped={lim.dropped} "
         f"(service={service*1e6:.0f}us)")
    assert p95 < 10 * service, "latency not bounded under overload"


def bench_tracer_overhead() -> None:
    """§5.1: tracing adds little; COMPILED_OUT removes it entirely."""
    n, stages = 3000, 3
    t_off = _run_chain(_chain_graph(stages, 4, 0.0, tracer=False), n,
                       f"s{stages}")
    t_on = _run_chain(_chain_graph(stages, 4, 0.0, tracer=True), n,
                      f"s{stages}")
    emit("tracer_off_per_packet", t_off / n * 1e6, "")
    emit("tracer_on_per_packet", t_on / n * 1e6,
         f"overhead x{t_on/t_off:.2f}")


def bench_detection_pipeline() -> None:
    """§6.1 Fig.-1 graph end-to-end."""
    import repro.calculators  # noqa: F401
    from repro.core import Graph, GraphBuilder
    b = GraphBuilder(num_threads=4)
    frame = b.input("frame")
    select = b.add_node("FrameSelectCalculator", name="select",
                        inputs={"IN": frame}, options={"every": 4})
    detect = b.add_node("ObjectDetectorCalculator", name="detect",
                        inputs={"FRAME": select.out("OUT", name="sel")},
                        options={"threshold": 0.5})
    reset = b.loopback()
    track = b.add_node("TrackerCalculator", name="track",
                       inputs={"FRAME": frame, "RESET": reset})
    merge = b.add_node("DetectionMergeCalculator", name="merge",
                       inputs={"DETECTIONS": detect.out("DETECTIONS",
                                                        name="det"),
                               "TRACKED": track.out("TRACKED", name="trk")})
    merged = merge.out("MERGED", name="merged")
    reset.tie(merge.out("RESET", name="reset"))
    annotate = b.add_node("AnnotationOverlayCalculator", name="annotate",
                          inputs={"FRAME": frame, "DETECTIONS": merged})
    b.output(annotate.out("ANNOTATED_FRAME", name="annotated"))
    g = Graph(b.build())
    done = []
    g.observe_output_stream("annotated", lambda p: done.append(p))
    g.start_run()
    rng = np.random.RandomState(0)
    frames = [(rng.rand(64, 64) * 255).astype(np.float32)
              for _ in range(60)]
    t0 = time.perf_counter()
    for t, f in enumerate(frames):
        g.add_packet_to_input_stream("frame", f, t)
    g.close_all_input_streams()
    g.wait_until_done(timeout=120)
    dt = time.perf_counter() - t0
    emit("detection_pipeline_per_frame", dt / len(frames) * 1e6,
         f"{len(frames)/dt:.0f} fps")


def bench_llm_serving() -> None:
    import dataclasses as dc
    import repro.calculators  # noqa: F401
    from repro.configs import get_config
    from repro.core import Graph
    from repro.serving import LLMEngine, build_serving_graph
    cfg = dc.replace(get_config("minicpm_2b").reduced(),
                     num_layers=2, d_model=128, vocab_size=512)
    engine = LLMEngine(cfg, max_len=64)
    engine.generate(np.zeros((4, 8), np.int32), 4)   # warm the jit cache
    g = Graph(build_serving_graph(batch_size=4),
              side_packets={"engine": engine})
    done = []
    g.observe_output_stream("responses", lambda p: done.append(p))
    g.start_run()
    rng = np.random.RandomState(0)
    n, new_toks = 24, 8
    t0 = time.perf_counter()
    for i in range(n):
        g.add_packet_to_input_stream("requests", {
            "tokens": rng.randint(0, 512, size=8).tolist(),
            "id": i, "max_new_tokens": new_toks}, i)
    g.close_all_input_streams()
    g.wait_until_done(timeout=300)
    dt = time.perf_counter() - t0
    emit("llm_serving_per_request", dt / n * 1e6,
         f"{n*new_toks/dt:.0f} tok/s, {len(done)}/{n} answered")


def bench_kernels() -> None:
    import jax
    import jax.numpy as jnp
    from repro.kernels.ops import flash_attention, rmsnorm
    from repro.kernels.ref import flash_attention_ref, rmsnorm_ref
    key = jax.random.PRNGKey(0)
    q = jax.random.normal(key, (2, 256, 8, 64), jnp.float32)
    k = jax.random.normal(key, (2, 256, 2, 64), jnp.float32)
    v = jax.random.normal(key, (2, 256, 2, 64), jnp.float32)

    def timeit(fn, *args, reps=5):
        fn(*args)  # compile
        t0 = time.perf_counter()
        for _ in range(reps):
            jax.block_until_ready(fn(*args))
        return (time.perf_counter() - t0) / reps * 1e6

    # the kernels run interpreted on the CPU (repro.kernels.ops)
    mode = "interpret" if jax.default_backend() == "cpu" else "compiled"
    t_kern = timeit(flash_attention, q, k, v)
    t_ref = timeit(jax.jit(flash_attention_ref), q, k, v)
    emit(f"flash_attention_{mode}", t_kern,
         f"oracle {t_ref:.0f}us ({jax.devices()[0].device_kind})")
    x = jax.random.normal(key, (512, 1024), jnp.float32)
    s = jnp.ones((1024,), jnp.float32)
    emit(f"rmsnorm_{mode}", timeit(rmsnorm, x, s),
         f"oracle {timeit(jax.jit(rmsnorm_ref), x, s):.0f}us")


def main() -> None:
    from repro.launch.compile_cache import enable_compile_cache
    enable_compile_cache()
    print("name,us_per_call,derived")
    for bench in (bench_scheduler_pipelining, bench_sync_policy_overhead,
                  bench_flow_limiter, bench_tracer_overhead,
                  bench_detection_pipeline, bench_llm_serving,
                  bench_kernels):
        try:
            bench()
        except Exception as e:  # noqa: BLE001
            import traceback
            traceback.print_exc()
            emit(bench.__name__ + "_FAILED", 0.0, repr(e))


if __name__ == '__main__':
    main()
