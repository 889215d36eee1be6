"""Attribute a profiler trace to the program's own spans and scopes.

The program opens ``jax.profiler.TraceAnnotation`` spans around the
phases of its serving loop (``serve.tick``, ``serve.decode``,
``engine.decode.sync``, ``graph.run`` ...; docs/OBSERVABILITY.md) and
puts ``jax.named_scope`` scopes around the layer kinds of its step
programs (``attn``, ``attn/gather``, ``ffn``, ``cache`` ...), which XLA
keeps in each instruction's ``metadata={op_name=...}``.  This module
reads both:

* :func:`load_xplane_stats` is :func:`trace.load_xplane` keeping each
  event's stats (span arguments) and each plane's;
* :func:`hlo_op_paths` reads each instruction's op path from a
  compiled step's text: a v5e trace names an op event by its HLO
  instruction and carries no op path;
* :func:`attribute` splits the device's idle time into the part under a
  ``serve.tick`` span and the rest, sums each step program's device
  *self* time by scope (an op's time less the part its nested ops
  cover, so a scan's ``while`` op is not counted on top of its body),
  and names the innermost phase of the engine's thread over every idle
  stretch.

Event times are nanoseconds from the start of the profile, host and
device alike, so ``[0, window_s]`` is the traced window.

Run as a module from the root of a checkout, it serves a cell with a
profiler slice, as ``bench/run.py --trace 1`` does, and prints the
attribution (no correctness check, no metrics of the benchmark)::

    python3 -m bench.attribution --workload <cell> --seed <n> \
        --seconds <s> [--out attribution.json]
"""
from __future__ import annotations

import bisect
import collections
import dataclasses
import glob
import os
import re
from typing import Any, Dict, List, Optional, Sequence, Tuple

from .trace import MODULES_LINE, OPS_LINE, _line, program_name, union

#: the scopes the step programs open (models/transformer.py,
#: models/attention.py), as op-path components
SCOPES = frozenset({"embed", "head", "cache", "norm", "attn", "mamba",
                    "mlstm", "slstm", "ffn", "ffn.moe", "gather",
                    "kv_write"})
#: prefixes of the program's own spans
SPAN_PREFIXES = ("serve.", "engine.", "graph.")
#: the program's spans around one step-program call, as the harness's
#: ``bench.prefill:<tokens>`` / ``bench.decode:<ctx>:<rows>`` are
STEP_SPANS = ("engine.prefill", "engine.decode")
UNSCOPED = "(unscoped)"
OUTSIDE = "(no span)"


def _plain(v):
    return v if isinstance(v, (int, float, str)) else str(v)


def load_xplane_stats(log_dir: str) -> Dict[str, Any]:
    """The newest ``*.xplane.pb`` under ``log_dir`` as plain data:
    ``{"planes": [{"name", "stats": {...}, "lines": [{"name",
    "events": [[name, start_ns, dur_ns, {stat: value}], ...]}]}]}``."""
    from jax.profiler import ProfileData
    paths = sorted(glob.glob(os.path.join(log_dir, "plugins", "profile",
                                          "*", "*.xplane.pb")))
    if not paths:
        raise FileNotFoundError(f"no xplane.pb under {log_dir}")
    data = ProfileData.from_file(paths[-1])
    planes = []
    for plane in data.planes:
        lines = [{"name": line.name,
                  "events": [[e.name, float(e.start_ns),
                              float(e.duration_ns),
                              {k: _plain(v) for k, v in e.stats}]
                             for e in line.events]}
                 for line in plane.lines]
        planes.append({"name": plane.name,
                       "stats": {k: _plain(v) for k, v in plane.stats},
                       "lines": lines})
    return {"planes": planes}


def hlo_name(event_name: str) -> str:
    """The HLO instruction an op event names: a v5e trace names each op
    event by the instruction's text (``%copy.57 = bf16[...] copy(...)``),
    whose first word is the name (``copy.57``)."""
    return event_name.split(" ", 1)[0].lstrip("%")


def scope_of(path: Optional[str]) -> str:
    """The named scopes in an op path, outermost first
    (``.../while/body/attn/gather/gather`` -> ``attn/gather``; the last
    component is the operation itself), or :data:`UNSCOPED`."""
    found = [p for p in (path or "").split("/")[:-1] if p in SCOPES]
    return "/".join(found) if found else UNSCOPED


_COMPUTATION = re.compile(r"^(?:ENTRY\s+)?%?([\w.\-]+)\s.*\{\s*$")
_INSTRUCTION = re.compile(r"^\s+(ROOT\s+)?%?([\w.\-]+)\s*=\s")
_OP_NAME = re.compile(r'op_name="([^"]*)"')
_CALLS = re.compile(r"calls=%?([\w.\-]+)")


def hlo_op_paths(text: str) -> Dict[str, str]:
    """Instruction name -> op path from a compiled module's text
    (``metadata={op_name=...}``), for traces whose op events carry no
    path.  An instruction without one (a fusion, mostly) takes the path
    of the computation it calls: its root's, else its first."""
    own: Dict[str, str] = {}
    calls: Dict[str, str] = {}
    comp_path: Dict[str, str] = {}
    comp = None
    for line in text.split("\n"):
        m = _INSTRUCTION.match(line)
        if m is None:
            c = _COMPUTATION.match(line)
            if c is not None:
                comp = c.group(1)
            continue
        name = m.group(2)
        path = _OP_NAME.search(line)
        if path is not None:
            own[name] = path.group(1)
            if comp is not None and (m.group(1) or comp not in comp_path):
                comp_path[comp] = path.group(1)
        callee = _CALLS.search(line)
        if callee is not None:
            calls[name] = callee.group(1)
    out = dict(own)
    for name, callee in calls.items():
        if name not in out and callee in comp_path:
            out[name] = comp_path[callee]
    return out


def self_times(events: Sequence[Tuple[float, float]]) -> List[float]:
    """Each interval's duration less the part its nested intervals
    cover (intervals nest or are disjoint, as ops on one line do)."""
    order = sorted(range(len(events)),
                   key=lambda i: (events[i][0], -events[i][1]))
    own = [float(d) for _, d in events]
    stack: List[int] = []
    for i in order:
        start, dur = events[i]
        while stack and start >= sum(events[stack[-1]]):
            stack.pop()
        if stack:
            own[stack[-1]] -= dur
        stack.append(i)
    return own


def _complement(busy: Sequence[Tuple[float, float]], lo: float,
                hi: float) -> List[Tuple[float, float]]:
    """The stretches of [lo, hi) that no interval of ``busy`` (merged,
    sorted) covers."""
    out, t = [], lo
    for s, e in busy:
        if e <= t:
            continue
        if s >= hi:
            break
        if s > t:
            out.append((t, s))
        t = max(t, e)
    if t < hi:
        out.append((t, hi))
    return out


def _overlap(stretches, covers) -> float:
    """Length of the stretches that the (merged, sorted) covers hold."""
    total, j = 0.0, 0
    for s, e in stretches:
        while j < len(covers) and covers[j][1] <= s:
            j += 1
        k = j
        while k < len(covers) and covers[k][0] < e:
            total += min(e, covers[k][1]) - max(s, covers[k][0])
            k += 1
    return total


def innermost(spans: Sequence[Tuple[float, float, str]]
              ) -> List[Tuple[float, float, str]]:
    """Cut one thread's nested spans into disjoint pieces, each named by
    the innermost span over it."""
    pieces: List[Tuple[float, float, str]] = []
    stack: List[Tuple[float, str]] = []       # (end, name)
    t = None

    def run_to(limit):
        nonlocal t
        while stack and stack[-1][0] <= limit:
            end, name = stack.pop()
            if end > t:
                pieces.append((t, end, name))
                t = end
        if stack and limit > t:
            pieces.append((t, limit, stack[-1][1]))
        t = max(t, limit)

    for start, end, name in sorted(spans, key=lambda s: (s[0], -s[1])):
        if t is None:
            t = start
        run_to(start)
        stack.append((end, name))
    if stack:
        run_to(max(e for e, _ in stack))
    return pieces


@dataclasses.dataclass
class Call:
    """One step-program execution and the host spans around it."""
    program: str
    seconds: float
    span: Optional[str]                  # the STEP_SPANS span over it
    args: Dict[str, Any]                 # that span's arguments
    annotation: Optional[str]            # a bench.* annotation over it


@dataclasses.dataclass
class Attribution:
    devices: int
    window_s: float
    busy_s: float                            # mean over devices
    #: device idle under a serve.tick span / outside every one (mean
    #: over devices); None when the trace holds no serve.tick span
    idle_in_tick_s: Optional[float]
    idle_between_ticks_s: Optional[float]
    #: program -> scope -> device self seconds; empty when no op of the
    #: trace carries a scope
    scopes: Dict[str, Dict[str, float]]
    executions: Dict[str, int]               # program -> executions
    #: idle seconds by the innermost span of the engine's thread
    phase_idle_s: Dict[str, float]
    calls: List[Call]
    #: program -> op name -> device self seconds of the ops whose path
    #: names no scope (copies that XLA inserts carry no path)
    unscoped: Dict[str, Dict[str, float]] = dataclasses.field(
        default_factory=dict)

    def scope_ms(self, program: str, prefix: str) -> Optional[float]:
        """Device self time per execution of ``program`` under the
        scopes that start with ``prefix`` (ms), or None."""
        by_scope = self.scopes.get(program)
        n = self.executions.get(program, 0)
        if not by_scope or not n:
            return None
        secs = sum(s for k, s in by_scope.items()
                   if k == prefix or k.startswith(prefix + "/"))
        return 1e3 * secs / n

    def scoped_share(self, program: str) -> Optional[float]:
        by_scope = self.scopes.get(program)
        total = sum(by_scope.values()) if by_scope else 0.0
        if total <= 0:
            return None
        return 1.0 - by_scope.get(UNSCOPED, 0.0) / total


def _span_name(name: str, stats: Dict[str, Any]) -> str:
    return f"{name}:{stats['node']}" if name == "graph.run" and \
        "node" in stats else name


def attribute(trace: Dict[str, Any], window_s: float,
              op_paths: Optional[Dict[str, Dict[str, str]]] = None
              ) -> Attribution:
    """Attribute a trace loaded by :func:`load_xplane_stats` and taken
    over ``window_s`` seconds.  ``op_paths`` (program -> instruction ->
    op path, :func:`hlo_op_paths`) names each op's scopes: a v5e trace's
    op events carry no op path of their own."""
    op_paths = op_paths or {}
    planes = trace["planes"]
    devices = [p for p in planes if p["name"].startswith("/device:")
               and (_line(p, OPS_LINE) or _line(p, MODULES_LINE))]
    if not devices:
        raise ValueError("the trace holds no device operations")
    hi = float(window_s) * 1e9
    # host spans: the program's, per thread, and the harness's
    threads: List[List[Tuple[float, float, str, Dict[str, Any]]]] = []
    bench: List[Tuple[float, float, str]] = []
    for plane in planes:
        if not plane["name"].startswith("/host"):
            continue
        for line in plane["lines"]:
            mine = []
            for name, start, dur, stats in line["events"]:
                if dur <= 0:
                    continue
                if name.startswith(SPAN_PREFIXES):
                    mine.append((start, start + dur, name, stats))
                elif name.startswith("bench."):
                    bench.append((start, start + dur, name))
            if mine:
                threads.append(mine)
    ticks = union([(s, e) for t in threads for s, e, n, _ in t
                    if n == "serve.tick"])
    engine = [t for t in threads if any(n == "serve.tick"
                                        for _, _, n, _ in t)]
    pieces = innermost([(s, e, _span_name(n, a)) for t in engine
                        for s, e, n, a in t])
    piece_starts = [p[0] for p in pieces]
    step_spans = sorted(((s, e, n, a) for t in threads
                         for s, e, n, a in t if n in STEP_SPANS),
                        key=lambda sp: sp[0])
    bench.sort()

    step_starts = [sp[0] for sp in step_spans]
    bench_starts = [sp[0] for sp in bench]

    def over(spans, starts, t):
        # the span over host time t (these do not nest: the last one
        # starting at or before t)
        i = bisect.bisect_right(starts, t) - 1
        return spans[i] if i >= 0 and t < spans[i][1] else None

    busy_total = in_tick = between = 0.0
    phase_idle: Dict[str, float] = collections.defaultdict(float)
    scopes: Dict[str, Dict[str, float]] = collections.defaultdict(
        lambda: collections.defaultdict(float))
    executions: Dict[str, int] = collections.defaultdict(int)
    unscoped: Dict[str, Dict[str, float]] = collections.defaultdict(
        lambda: collections.defaultdict(float))
    calls: List[Call] = []
    any_scope = False
    for dev in devices:
        modules = _line(dev, MODULES_LINE) or []
        mod_iv = sorted((ev[1], ev[1] + ev[2], program_name(ev[0]))
                        for ev in modules)
        mod_starts = [m[0] for m in mod_iv]
        for start, end, prog in mod_iv:
            executions[prog] += 1
            mid = (start + end) / 2
            sp = over(step_spans, step_starts, mid)
            ann = over(bench, bench_starts, mid)
            calls.append(Call(prog, (end - start) * 1e-9,
                              sp[2] if sp else None,
                              dict(sp[3]) if sp else {},
                              ann[2] if ann else None))
        ops = _line(dev, OPS_LINE) or modules
        own = self_times([(ev[1], ev[2]) for ev in ops])
        for ev, secs in zip(ops, own):
            name, start, _dur, stats = ev[:4]
            i = bisect.bisect_right(mod_starts, start) - 1
            prog = mod_iv[i][2] if i >= 0 and start < mod_iv[i][1] \
                else "?"
            scope = scope_of(op_paths.get(prog, {}).get(hlo_name(name)))
            any_scope |= scope != UNSCOPED
            scopes[prog][scope] += secs * 1e-9
            if scope == UNSCOPED:
                unscoped[prog][name.split("{", 1)[0]] += secs * 1e-9
        busy = union([(ev[1], ev[1] + ev[2]) for ev in ops])
        busy_total += sum(min(e, hi) - max(s, 0.0) for s, e in busy
                          if e > 0 and s < hi) * 1e-9
        idle = _complement(busy, 0.0, hi)
        inside = _overlap(idle, ticks)
        in_tick += inside * 1e-9
        between += (sum(e - s for s, e in idle) - inside) * 1e-9
        for s, e in idle:
            # the pieces of the engine's thread over this idle stretch
            j = max(0, bisect.bisect_right(piece_starts, s) - 1)
            covered = 0.0
            while j < len(pieces) and pieces[j][0] < e:
                ps, pe, name = pieces[j]
                part = min(e, pe) - max(s, ps)
                if part > 0:
                    phase_idle[name] += part * 1e-9
                    covered += part
                j += 1
            phase_idle[OUTSIDE] += (e - s - covered) * 1e-9
    n = len(devices)
    has_ticks = bool(ticks)
    return Attribution(
        devices=n, window_s=float(window_s), busy_s=busy_total / n,
        idle_in_tick_s=in_tick / n if has_ticks else None,
        idle_between_ticks_s=between / n if has_ticks else None,
        scopes={p: dict(v) for p, v in scopes.items()} if any_scope
        else {},
        executions=dict(executions),
        phase_idle_s={k: v / n for k, v in phase_idle.items() if v > 0},
        calls=calls,
        unscoped={p: dict(v) for p, v in unscoped.items()})


def summary_line(att: Attribution, program: str) -> str:
    """One log line: the idle split, the top idle phases, one step
    program's device time per execution by scope and its largest ops
    that carry no scope."""
    parts = [f"attribution: window {att.window_s:.3f}s, busy "
             f"{att.busy_s:.3f}s"]
    if att.idle_in_tick_s is not None:
        parts.append(f"idle in tick {att.idle_in_tick_s:.4f}s, between "
                     f"ticks {att.idle_between_ticks_s:.4f}s")
    top = sorted(att.phase_idle_s.items(), key=lambda kv: -kv[1])[:6]
    parts.append("idle by phase " + ", ".join(
        f"{k} {1e3 * v:.1f}ms" for k, v in top))
    n = att.executions.get(program, 0)
    by_scope = att.scopes.get(program, {})
    if n and by_scope:
        total = sum(by_scope.values())
        parts.append(f"{program} x{n}: " + ", ".join(
            f"{k} {1e3 * v / n:.2f}ms ({100 * v / total:.1f}%)"
            for k, v in sorted(by_scope.items(), key=lambda kv: -kv[1])))
        worst = sorted(att.unscoped.get(program, {}).items(),
                       key=lambda kv: -kv[1])[:4]
        parts.append("unscoped " + ", ".join(
            f"{k} {1e3 * v / n:.2f}ms" for k, v in worst))
    return "; ".join(parts)


def decode_op_paths(setup) -> Dict[str, str]:
    """:func:`hlo_op_paths` of the paged decode step a served cell ran,
    compiled again from the same shapes: the same program gets the same
    instruction names (``decode_pathless_ms`` in :func:`main`'s output
    bounds the device time they missed)."""
    import jax
    import jax.numpy as jnp
    engine, st = setup.engine, setup.settings
    n, bs = st["num_slots"], st["block_size"]

    def spec(shape, dtype):
        return jax.ShapeDtypeStruct(shape, dtype)

    step = engine._serve[("paged", bs)]["decode"]
    compiled = step.lower(
        engine.params, spec((n, 1), jnp.int32),
        engine.model.abstract_paged_cache(st["num_blocks"], bs),
        spec((n,), jnp.int32), spec((n,), jnp.bool_),
        spec((n, st["max_len"] // bs), jnp.int32)).compile()
    return hlo_op_paths(compiled.as_text())


def main(argv=None) -> int:
    import argparse
    import json
    import shutil
    import sys
    import tempfile
    import time
    import types
    t_start = time.perf_counter()
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--out", default=None,
                    help="write the whole attribution here as JSON")
    args = ap.parse_args(argv)
    root = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    sys.path.insert(0, os.path.join(root, "src"))
    from . import harness, readers, spec
    from . import trace as trace_mod

    cell = spec.load_cell(args.workload)
    harness.enable_compile_cache()
    setup = harness.prepare(cell, args.seed, trace=True, t_start=t_start)
    trace_dir = tempfile.mkdtemp(prefix="bench-attribution-")
    out = harness.serve(setup, args.seed, args.seconds, t_start=t_start,
                        trace_dir=trace_dir)
    window = out["trace_stop"] - out["trace_start"]
    loaded = load_xplane_stats(trace_dir)
    shutil.rmtree(trace_dir, ignore_errors=True)
    decode = readers.PROGRAMS["decode"]
    paths = decode_op_paths(setup)
    att = attribute(loaded, window, op_paths={decode: paths})
    harness.log_stderr(summary_line(att, decode))
    n_decode = max(1, att.executions.get(decode, 0))
    summary = trace_mod.reduce(
        {"planes": [{"name": p["name"], "lines": [
            {"name": ln["name"], "events": [ev[:3] for ev in ln["events"]]}
            for ln in p["lines"]]} for p in loaded["planes"]]}, window)
    run = types.SimpleNamespace(reg0=out.get("reg0", {}),
                                reg1=out.get("reg1", {}))
    # every step-program execution against the harness's annotation of
    # the same call: [executions whose span names it, executions]
    as_bench = {"engine.prefill": "bench.prefill:{tokens}",
                "engine.decode": "bench.decode:{ctx}:{rows}"}
    matched = {prog: [0, 0] for prog in readers.PROGRAMS.values()}
    for c in att.calls:
        if c.program in matched:
            want = as_bench.get(c.span, "").format_map(
                collections.defaultdict(str, c.args))
            matched[c.program][0] += int(c.annotation == want)
            matched[c.program][1] += 1

    def span_ms(name):
        durs = [ev[2] for p in loaded["planes"] for ln in p["lines"]
                for ev in ln["events"] if ev[0] == name]
        return 1e-6 * sum(durs) / len(durs) if durs else None

    doc = {
        "workload": cell.name, "seed": args.seed,
        "window_s": window, "busy_s": att.busy_s,
        "device_idle_pct": 100.0 * summary.idle_share,
        "idle_in_tick_pct": None if att.idle_in_tick_s is None
        else 100.0 * att.idle_in_tick_s / window,
        "idle_between_ticks_pct": None if att.idle_between_ticks_s is None
        else 100.0 * att.idle_between_ticks_s / window,
        "decode_attn_ms": att.scope_ms(readers.PROGRAMS["decode"], "attn"),
        "decode_scoped_share": att.scoped_share(readers.PROGRAMS["decode"]),
        # device time of decode ops to which the compiled text gives no
        # op path: copies XLA inserts, or names the recompile lacks
        "decode_pathless_ms": sum(
            v for k, v in att.unscoped.get(decode, {}).items()
            if hlo_name(k) not in paths) * 1e3 / n_decode,
        "decode_step_ms": readers.window_mean(run, "serve.decode_step_ms"),
        "tick_ms": span_ms("serve.tick"),
        "engine_decode_ms": span_ms("engine.decode"),
        "engine_compiles_in_window": spec.load_reader(
            "engine_compiles.rate")(run),
        "spans_match_annotations": matched,
        "executions": att.executions,
        "scopes_ms_per_execution": {
            p: {k: 1e3 * v / max(1, att.executions.get(p, 0))
                for k, v in s.items()} for p, s in att.scopes.items()},
        "unscoped_ms_per_execution": {
            p: dict(sorted(((k, 1e3 * v / max(1, att.executions.get(p, 0)))
                            for k, v in u.items()),
                           key=lambda kv: -kv[1])[:12])
            for p, u in att.unscoped.items()},
        "phase_idle_ms": {k: 1e3 * v for k, v in att.phase_idle_s.items()},
        "top_ops": summary.top_ops, "idle_gaps": summary.idle_gaps}
    if args.out:
        os.makedirs(os.path.dirname(os.path.abspath(args.out)),
                    exist_ok=True)
        with open(args.out, "w") as f:
            json.dump(doc, f, indent=1)
    print(json.dumps({k: doc[k] for k in (
        "workload", "seed", "window_s", "device_idle_pct",
        "idle_in_tick_pct", "idle_between_ticks_pct", "decode_attn_ms",
        "decode_scoped_share", "decode_pathless_ms", "decode_step_ms",
        "tick_ms",
        "engine_compiles_in_window", "spans_match_annotations")}),
        flush=True)
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
