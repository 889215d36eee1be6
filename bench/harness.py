"""Run one cell: set-up, the measured window, the check, the result line.

Set-up (``setup_s``, from process start to the first request of the
traffic): JAX and the device, the weights made on the chip from the
seed, the engine and its paged ``GraphServer``, and one warm-up request
per prompt length of the mix, which compiles (or loads from the
persistent cache) every program the window will run: one prefill per
pool length, the insert and the decode step.

The traffic then runs through ``AsyncFrontend.stream``, the async front
door, from one asyncio loop in this process.  The first ``ramp_s``
seconds fill the server; the window is the ``seconds`` after them.  An
open-loop mix sends each request at its due time; a closed-loop mix
keeps ``clients_per_slot`` clients per slot, each sending its next
request when the last one ends.  No request is sent after the window.
The run then waits (``grace_s`` at most) until every request sent has
its first token, so that each time to first token is whole, and stops
waiting for the rest of the streams; a request with no first token by
then, or one that raised, counts as failed.

With ``trace`` on, the profiler records a slice of the window
(``TRACE_SECONDS`` in its middle); engine calls carry host annotations
naming their shapes, so the trace reduction can price every device
program it sees.  After the traffic the server is closed, the device
peak memory is read, and the reference scores a sample of what was
served (``correct.py``).
"""
from __future__ import annotations

import asyncio
import dataclasses
import gc
import json
import os
import sys
import tempfile
import time
from typing import Any, Callable, Dict, List, Optional, Tuple

from . import correct as correct_mod
from . import shapes
from . import spec as spec_mod
from . import traffic as traffic_mod
from .stats import Record

#: seconds of the window the profiler records in a traced run
TRACE_SECONDS = 6.0
#: prefix of the host annotations the harness puts around engine calls
ANNOTATION = "bench."


def log_stderr(*parts) -> None:
    print(*parts, file=sys.stderr, flush=True)


class CompileClock:
    """Host times of JAX's backend compiles (a compile or a load from the
    persistent cache: a new executable either way), and the persistent
    cache's hits and misses."""

    def __init__(self):
        import jax
        from jax._src import dispatch
        self._event = dispatch.BACKEND_COMPILE_EVENT
        self.stamps: List[float] = []
        self.seconds = 0.0
        self.cache = {"hits": 0, "misses": 0}
        jax.monitoring.register_event_duration_secs_listener(self._on_dur)
        jax.monitoring.register_event_listener(self._on_event)

    def _on_dur(self, event, secs, **_):
        if event == self._event:
            self.stamps.append(time.perf_counter())
            self.seconds += secs

    def _on_event(self, event, **_):
        if event == "/jax/compilation_cache/cache_hits":
            self.cache["hits"] += 1
        elif event == "/jax/compilation_cache/cache_misses":
            self.cache["misses"] += 1

    def between(self, t0: float, t1: float) -> int:
        return sum(1 for s in self.stamps if t0 <= s < t1)


class GcClock:
    """Host times of the Python garbage collector's passes (start,
    seconds, generation), to tell its pauses from the server's."""

    def __init__(self):
        self.passes: List[Tuple[float, float, int]] = []
        self._start: Optional[float] = None
        gc.callbacks.append(self._on_gc)

    def _on_gc(self, phase, info):
        now = time.perf_counter()
        if phase == "start":
            self._start = now
        elif self._start is not None:
            self.passes.append((self._start, now - self._start,
                                int(info["generation"])))
            self._start = None

    def close(self) -> None:
        gc.callbacks.remove(self._on_gc)

    def summary(self, t0: float, t1: float) -> str:
        inside = [p for p in self.passes if t0 <= p[0] < t1]
        if not inside:
            return "gc: no collection in the window"
        start, secs, gen = max(inside, key=lambda p: p[1])
        return (f"gc: {len(inside)} collections in the window "
                f"({sum(1 for p in inside if p[2] == 2)} of generation 2), "
                f"{1e3 * sum(p[1] for p in inside):.1f} ms in all; longest "
                f"{1e3 * secs:.1f} ms (generation {gen}) at "
                f"+{start - t0:.2f}s")


def enable_compile_cache() -> str:
    """JAX's persistent compilation cache: ``JAX_COMPILATION_CACHE_DIR``
    when set (JAX reads it itself), else ``<checkout>/.jax_cache``, a
    fixed path.  Every program is cached, however fast it compiled."""
    import jax
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    env = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if env:
        return env
    path = str(spec_mod.ROOT / ".jax_cache")
    jax.config.update("jax_compilation_cache_dir", path)
    return path


@dataclasses.dataclass
class Run:
    """Everything a metric reader may read."""
    seconds: float
    t0: float                                 # window start (host clock)
    t1: float                                 # window end
    gave_up: float                    # end of the wait for first tokens
    setup_s: float
    records: List[Record]
    compiles: CompileClock
    reg0: Dict[str, Any]                      # server metrics/stats at t0
    reg1: Dict[str, Any]                      # server metrics/stats at t1
    stats: Dict[str, Any]                     # server stats after the run
    usable_blocks: int
    dims: shapes.Dims
    peaks: Dict[str, Any]
    trace: Optional[Any] = None               # trace.Summary


def serving_settings(cell: spec_mod.Cell) -> Dict[str, int]:
    """Arena and slots of a cell: the configuration's arena for the
    mix's ``max_len``, and as many slots as the arena holds requests of
    the mix's mean size."""
    mix, serving = cell.traffic, cell.config["serving"]
    max_len = int(mix["max_len"])
    block = int(serving["block_size"])
    arena = int(serving["arena_tokens"][str(max_len)])
    mean = traffic_mod.mean_request_tokens(mix)
    return {"max_len": max_len, "block_size": block,
            "num_blocks": arena // block + 1,
            "num_slots": max(1, int(arena // mean))}


def _annotate_calls(engine) -> None:
    """Wrap the engine's prefill and decode in host annotations naming
    their shapes (``bench.prefill:<prompt>``,
    ``bench.decode:<sum of live context>:<rows>``).  The trace reduction
    prices each device program by the annotation around it."""
    import jax
    import numpy as np
    prefill, decode = engine.prefill, engine.decode

    def rec_prefill(tokens):
        name = f"{ANNOTATION}prefill:{int(np.shape(tokens)[1])}"
        with jax.profiler.TraceAnnotation(name):
            return prefill(tokens)

    def rec_decode(backend, cache, last_tokens, positions, active,
                   block_tables=None):
        act = np.asarray(active, bool)
        ctx = int((np.asarray(positions)[act] + 1).sum())
        name = f"{ANNOTATION}decode:{ctx}:{int(act.sum())}"
        with jax.profiler.TraceAnnotation(name):
            return decode(backend, cache, last_tokens, positions, active,
                          block_tables)

    engine.prefill, engine.decode = rec_prefill, rec_decode


def _snapshot(server) -> Dict[str, Any]:
    """The server's metrics registry and counters, read while it runs."""
    for _ in range(10):
        try:
            return {"metrics": server.metrics(), "stats": server.stats()}
        except RuntimeError:        # a registry grew while being read
            time.sleep(0.001)
    return {"metrics": server.metrics(), "stats": server.stats()}


async def _consume(front, prompt, req, rec: Record):
    try:
        async for tok in front.stream(prompt, max_new_tokens=req.max_new,
                                      request_id=f"r{req.index}"):
            rec.stamps.append(time.perf_counter())
            rec.tokens.append(int(tok))
        rec.done = True
    except asyncio.CancelledError:
        rec.cut = True             # the run stopped waiting for it
        raise
    except Exception as e:  # noqa: BLE001 - a failed request is counted
        rec.error = f"{type(e).__name__}: {e}"


async def _sleep_until(t: float) -> None:
    d = t - time.perf_counter()
    if d > 0:
        await asyncio.sleep(d)


async def _drive(front, server, cell, seed, seconds, vocab, t_base,
                 trace_dir) -> Dict[str, Any]:
    mix = cell.traffic
    ramp, grace = float(mix["ramp_s"]), float(mix["grace_s"])
    t0 = t_base + ramp
    t1 = t0 + seconds
    deadline = t1 + grace
    out: Dict[str, Any] = {"t0": t0, "t1": t1, "records": []}
    records: List[Record] = out["records"]
    loop = asyncio.get_running_loop()
    tasks: List[asyncio.Task] = []

    async def marks():
        await _sleep_until(t0)
        out["reg0"] = _snapshot(server)
        if trace_dir is not None:
            import jax
            opts = jax.profiler.ProfileOptions()
            opts.python_tracer_level = 0
            span = min(TRACE_SECONDS, seconds)
            await _sleep_until(t0 + (seconds - span) / 2)
            await loop.run_in_executor(None, lambda: jax.profiler.start_trace(
                trace_dir, profiler_options=opts))
            out["trace_start"] = time.perf_counter()
            await _sleep_until(out["trace_start"] + span)
            out["trace_stop"] = time.perf_counter()
            await loop.run_in_executor(None, jax.profiler.stop_trace)
        await _sleep_until(t1)
        out["reg1"] = _snapshot(server)

    if mix["loop"] == "open":
        reqs = traffic_mod.open_loop(mix, seed, seconds)
        prompts = [r.tokens(vocab) for r in reqs]
        mark_task = loop.create_task(marks())
        for req, prompt in zip(reqs, prompts):
            rec = Record(req.index, req.prompt_len, req.max_new,
                         due=t_base + req.due)
            records.append(rec)
            await _sleep_until(rec.due)
            tasks.append(loop.create_task(_consume(front, prompt, req, rec)))
    elif mix["loop"] == "closed":
        seq = traffic_mod.closed_loop(mix, seed)
        clients = int(mix["clients_per_slot"]) * \
            serving_settings(cell)["num_slots"]

        async def client():
            while time.perf_counter() < t1:
                req = next(seq)
                rec = Record(req.index, req.prompt_len, req.max_new,
                             due=time.perf_counter())
                records.append(rec)
                await _consume(front, req.tokens(vocab), req, rec)

        mark_task = loop.create_task(marks())
        tasks = [loop.create_task(client()) for _ in range(clients)]
    else:
        raise ValueError(f"unknown loop {mix['loop']!r}")
    await mark_task
    # After the window: wait (``grace_s`` at most) until every request
    # sent has its first token, so each time to first token is whole;
    # then stop waiting for the rest of the streams.  A request with no
    # first token by then never came.
    while (time.perf_counter() < deadline
           and any(not r.stamps and r.error is None and not r.done
                   for r in records)):
        await asyncio.sleep(0.05)
    out["gave_up"] = time.perf_counter()
    pending = [t for t in tasks if not t.done()]
    for t in pending:
        t.cancel()
    if pending:
        await asyncio.wait(pending, timeout=60.0)
    return out


def _warm_up(server, cell, vocab: int, timeout: float) -> None:
    """One request per prompt length of the mix, all at once: compiles
    (or loads) every prefill length, the insert and the decode step."""
    import numpy as np
    rng = np.random.default_rng(12345)
    pool = traffic_mod.prompt_pool(cell.traffic["prompt"])
    handles = [server.submit(rng.integers(0, vocab, n, dtype=np.int32),
                             max_new_tokens=2, request_id=f"warm{i}")
               for i, n in enumerate(pool)]
    for h in handles:
        h.result(timeout=timeout)


def _peak_bytes(devices) -> Optional[int]:
    peaks = [(d.memory_stats() or {}).get("peak_bytes_in_use")
             for d in devices]
    peaks = [p for p in peaks if p is not None]
    return max(peaks) if peaks else None


@dataclasses.dataclass
class Setup:
    """A cell's system under test, built once per process."""
    cell: spec_mod.Cell
    settings: Dict[str, int]
    dims: shapes.Dims
    vocab: int
    abstract: Any                   # the program's parameter shapes
    params: Any                     # the weights the program is handed
    engine: Any
    clock: CompileClock
    devices: List[Any]
    peaks: Dict[str, Any]


def prepare(cell: spec_mod.Cell, seed: int, *, trace: bool, t_start: float,
            require_tpu: bool = True, peaks: Optional[Dict[str, Any]] = None,
            fault: Optional[Callable[[Any], None]] = None,
            log=log_stderr) -> Setup:
    """Device check, weights from ``seed`` and the engine.

    ``require_tpu=False`` and ``fault`` exist for the benchmark's own
    tests: the first skips the device check, the second breaks the
    engine underneath (it is called with the engine)."""
    import jax
    from repro.models.model import Model
    from repro.serving import LLMEngine

    from .program import arch_config

    if require_tpu:
        peaks = spec_mod.check_devices(jax.devices(), cell.chips)
    log(f"device ready at {time.perf_counter() - t_start:.2f}s")
    clock = CompileClock()
    settings = serving_settings(cell)
    arch = arch_config(cell.config)
    abstract = Model(arch).abstract()
    params = program_weights(cell, abstract, seed)
    log(f"weights ready at {time.perf_counter() - t_start:.2f}s")
    engine = LLMEngine(arch, params=params, max_len=settings["max_len"])
    if trace:
        _annotate_calls(engine)
    if fault is not None:
        fault(engine)
    return Setup(cell=cell, settings=settings,
                 dims=shapes.Dims.of(cell.config),
                 vocab=int(cell.config["config"]["vocab_size"]),
                 abstract=abstract, params=params, engine=engine,
                 clock=clock, devices=jax.devices()[:max(1, cell.chips)],
                 peaks=peaks or {})


def program_weights(cell: spec_mod.Cell, abstract, seed: int):
    """The model drawn from ``seed``, folded into the weights the
    program runs (``weights.for_program``)."""
    import jax

    from . import weights as weights_mod
    params = weights_mod.for_program(
        weights_mod.make(abstract, seed, cell.config), cell.config)
    jax.block_until_ready(params)
    return params


def reference_weights(setup: Setup, seed: int):
    """The model as drawn from ``seed``, for the reference.  The
    program's copy is freed first and the model made anew, so the
    reference reads nothing the program was handed and the two copies
    never share the chip."""
    import jax

    from . import weights as weights_mod
    setup.params = None
    if setup.engine is not None:
        setup.engine.params = None
    gc.collect()
    model = weights_mod.make(setup.abstract, seed, setup.cell.config)
    jax.block_until_ready(model)
    return model


def serve(setup: Setup, seed: int, seconds: float, *, t_start: float,
          trace_dir: Optional[str] = None,
          log=log_stderr) -> Dict[str, Any]:
    """A paged ``GraphServer`` on the engine, warmed up, then the
    traffic; returns what ``_drive`` saw plus the server's counters, the
    device memory peak and ``setup_s``.  The server is closed after."""
    from repro.serving import AsyncFrontend, GraphServer, Policy
    cell, settings, mix = setup.cell, setup.settings, setup.cell.traffic
    server = GraphServer(setup.engine, num_slots=settings["num_slots"],
                         backend="paged",
                         block_size=settings["block_size"],
                         num_blocks=settings["num_blocks"],
                         admission="reserve", queue_size=1 << 14,
                         max_new_tokens=int(mix["output"]["max"]))
    _warm_up(server, cell, setup.vocab, timeout=1200.0)
    clock = setup.clock
    log(f"warm at {time.perf_counter() - t_start:.2f}s; compiles "
        f"{len(clock.stamps)} ({clock.seconds:.2f}s), persistent cache "
        f"{clock.cache}; serving {settings}")
    front = AsyncFrontend(server, policy=Policy(timeout_ms=1e3 * (
        float(mix["ramp_s"]) + seconds + float(mix["grace_s"]) + 120)))
    gc.collect()
    gc_clock = GcClock()
    t_base = time.perf_counter()
    try:
        out = asyncio.run(_drive(front, server, cell, seed, seconds,
                                 setup.vocab, t_base, trace_dir))
    finally:
        gc_clock.close()
    out["gc"] = gc_clock.summary(out["t0"], out["t1"])
    out["setup_s"] = t_base - t_start
    out["stats"] = server.stats()
    out["memory_peak"] = _peak_bytes(setup.devices)
    server.close(timeout=120.0)
    del front, server
    gc.collect()
    return out


def score(setup: Setup, records: List[Record], seed: int,
          modes=("f32",)) -> Tuple[List[Record], Dict[str, List[Any]]]:
    """The sample of served requests and, per reference mode, the gap of
    each of its tokens (``correct.served_gaps``, or
    ``correct.control_gaps`` for ``fp8``).  Frees the program's weights
    (``reference_weights``)."""
    import numpy as np
    mix = setup.cell.traffic
    picked = correct_mod.sample(
        records, int(setup.cell.limits["sample_requests"]), seed)
    model = reference_weights(setup, seed)
    scorer = correct_mod.Scorer(setup.cell.config, setup.settings["max_len"],
                                int(mix["output"]["max"]), modes=modes)
    fns = {"f32": correct_mod.served_gaps, "fp8": correct_mod.control_gaps}
    gaps = {m: [fns[m](scorer, model, _prompt_of(r, seed, setup.vocab),
                       np.asarray(r.tokens, np.int32)) for r in picked]
            for m in modes}
    return picked, gaps


def run_cell(cell: spec_mod.Cell, seed: int, seconds: float, trace: bool,
             *, t_start: float, require_tpu: bool = True,
             peaks: Optional[Dict[str, Any]] = None,
             fault: Optional[Callable[[Any], None]] = None,
             log=log_stderr) -> Dict[str, Any]:
    """Run ``cell`` once and return the result line's object."""
    setup = prepare(cell, seed, trace=trace, t_start=t_start,
                    require_tpu=require_tpu, peaks=peaks, fault=fault,
                    log=log)
    trace_dir = tempfile.mkdtemp(prefix="bench-trace-") if trace else None
    out = serve(setup, seed, seconds, t_start=t_start, trace_dir=trace_dir,
                log=log)
    setup.engine = None
    gc.collect()
    records: List[Record] = out["records"]
    log(longest_stall(records, out["t0"], out["t1"]))
    log(out["gc"])
    run = Run(seconds=seconds, t0=out["t0"],
              t1=out["t1"], gave_up=out["gave_up"], setup_s=out["setup_s"],
              records=records, compiles=setup.clock,
              reg0=out.get("reg0", {}), reg1=out.get("reg1", {}),
              stats=out["stats"],
              usable_blocks=setup.settings["num_blocks"] - 1,
              dims=setup.dims, peaks=setup.peaks)

    t_ref = time.perf_counter()
    picked, gaps = score(setup, records, seed)
    ok, checks = correct_mod.check(records, picked, gaps["f32"],
                                   setup.vocab, cell.limits)
    log(f"reference: {len(picked)} requests, "
        f"{sum(len(g) for g in gaps['f32'])} tokens in "
        f"{time.perf_counter() - t_ref:.2f}s")

    dev = setup.devices[0]
    result: Dict[str, Any] = {
        "correct": bool(ok), "attempted": len(records),
        "failed": checks["failed_requests"]["value"], "metrics": {},
        "device": {"platform": dev.platform, "kind": dev.device_kind,
                   "count": len(setup.devices),
                   "memory_peak_bytes": out["memory_peak"]}}
    if trace:
        _reduce_trace(run, out, trace_dir, result, log)
    for m in cell.metrics:
        if m.end_to_end == (not trace):
            value = m.read(run)
            if value is not None:
                result["metrics"][m.name] = {"value": float(value),
                                             "unit": m.unit}
    result["checks"] = checks
    return result


def _reduce_trace(run: Run, out, trace_dir: str, result, log) -> None:
    import shutil

    from . import trace as trace_mod
    t_tr = time.perf_counter()
    window = out["trace_stop"] - out["trace_start"]
    loaded = trace_mod.load_xplane(trace_dir)
    shutil.rmtree(trace_dir, ignore_errors=True)
    log("trace planes: " + "; ".join(
        f"{p['name']} [{', '.join(ln['name'] for ln in p['lines'])}]"
        for p in loaded["planes"] if not p["name"].startswith("/host")))
    summary = trace_mod.reduce(loaded, window, host_prefixes=(ANNOTATION,))
    run.trace = summary
    result["device"]["busy_s"] = summary.busy_s
    result["device"]["window_s"] = summary.window_s
    result["breakdown"] = {
        "device_ops": [[n, s] for n, s in summary.top_ops],
        "idle_gaps": [[n, s] for n, s in summary.idle_gaps]}
    top = sorted(summary.programs.items(), key=lambda kv: -kv[1][1])[:6]
    log(f"trace: {summary.devices} device(s), window {window:.3f}s, busy "
        f"{summary.busy_s:.3f}s, programs {top}, priced calls "
        f"{summary.priced}/{len(summary.calls)}; reduced in "
        f"{time.perf_counter() - t_tr:.2f}s")


def longest_stall(records: List[Record], t0: float, t1: float) -> str:
    """The longest time in the window in which no request streamed a
    token, and when it began: a pause of the whole server shows here."""
    import numpy as np
    st = np.sort([s for r in records for s in r.stamps if t0 <= s < t1])
    if len(st) < 2:
        return "window: fewer than two tokens streamed"
    gaps = np.diff(st)
    i = int(gaps.argmax())
    return (f"window: {len(st)} tokens; longest stall {1e3 * gaps[i]:.1f} "
            f"ms at +{st[i] - t0:.2f}s; median gap "
            f"{1e3 * float(np.median(gaps)):.2f} ms")


def _prompt_of(rec: Record, seed: int, vocab: int):
    req = traffic_mod.Request(rec.index, rec.prompt_len, rec.max_new,
                              None, seed)
    return req.tokens(vocab)


def check_lines(checks: Dict[str, Dict]) -> List[str]:
    lines = []
    for name, c in checks.items():
        bound = f"limit {c['limit']}" if "limit" in c else f"min {c['min']}"
        lines.append(f"check {name} {c['value']} {bound}")
    return lines


def emit(result: Dict[str, Any]) -> None:
    """The numbers compared on the last lines of stderr, the result as
    the last line of stdout."""
    for line in check_lines(result["checks"]):
        print(line, file=sys.stderr)
    sys.stderr.flush()
    print(json.dumps(result), flush=True)
