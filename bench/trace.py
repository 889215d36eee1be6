"""Reduce a JAX profiler trace to device busy time, per-program device
time, the top device operations and the longest idle gaps.

The profiler writes ``<dir>/plugins/profile/<time>/*.xplane.pb``;
:func:`load_xplane` turns it into plain data::

    {"planes": [{"name": "/device:TPU:0",
                 "lines": [{"name": "XLA Modules",
                            "events": [[name, start_ns, dur_ns], ...]},
                           {"name": "XLA Ops", "events": [...]}]},
                {"name": "/host:CPU", "lines": [...]}]}

and :func:`reduce` works on that form only, so it can be checked on a
small recorded trace.  Device planes are those named ``/device:...``.
A device's busy time is the union of its ``XLA Ops`` intervals (its
``XLA Modules`` intervals where ops are absent); a program's device time
is the sum of its ``XLA Modules`` events, named by the jitted function
(``jit_<name>``, any ``(<id>)`` suffix dropped).  Each program execution
and each idle gap is matched to the host annotation (a span whose name
starts with one of ``host_prefixes``, the harness's own) that covers its
midpoint; a gap that none covers is named ``host``.
"""
from __future__ import annotations

import bisect
import collections
import dataclasses
import glob
import os
import re
from typing import Any, Dict, List, Optional, Sequence, Tuple

OPS_LINE = "XLA Ops"
MODULES_LINE = "XLA Modules"
_SUFFIX = re.compile(r"\(\d+\)$")


def load_xplane(log_dir: str) -> Dict[str, Any]:
    from jax.profiler import ProfileData
    paths = sorted(glob.glob(os.path.join(log_dir, "plugins", "profile",
                                          "*", "*.xplane.pb")))
    if not paths:
        raise FileNotFoundError(f"no xplane.pb under {log_dir}")
    data = ProfileData.from_file(paths[-1])
    planes = []
    for plane in data.planes:
        lines = []
        for line in plane.lines:
            lines.append({"name": line.name,
                          "events": [[e.name, float(e.start_ns),
                                      float(e.duration_ns)]
                                     for e in line.events]})
        planes.append({"name": plane.name, "lines": lines})
    return {"planes": planes}


def program_name(event_name: str) -> str:
    return _SUFFIX.sub("", event_name)


def union(intervals: Sequence[Tuple[float, float]]
          ) -> List[Tuple[float, float]]:
    """Merge [start, end) intervals."""
    out: List[List[float]] = []
    for s, e in sorted(intervals):
        if out and s <= out[-1][1]:
            out[-1][1] = max(out[-1][1], e)
        else:
            out.append([s, e])
    return [(s, e) for s, e in out]


@dataclasses.dataclass
class Summary:
    devices: int
    window_s: float
    busy_s: float                        # mean over devices
    programs: Dict[str, Tuple[int, float]]   # name -> (calls, seconds)
    top_ops: List[Tuple[str, float]]
    idle_gaps: List[Tuple[str, float]]
    #: every program execution: (program, device seconds, annotation
    #: around it on the host, or None)
    calls: List[Tuple[str, float, Optional[str]]]

    @property
    def priced(self) -> int:
        return sum(1 for c in self.calls if c[2] is not None)

    @property
    def idle_share(self) -> float:
        return max(0.0, 1.0 - self.busy_s / self.window_s)

    def program_seconds(self, match: str) -> Tuple[int, float]:
        """(calls, device seconds) of the programs whose name contains
        ``match``."""
        calls = secs = 0
        for name, (n, s) in self.programs.items():
            if match in name:
                calls += n
                secs += s
        return calls, secs


def _line(plane, name):
    for line in plane["lines"]:
        if line["name"] == name:
            return line["events"]
    return None


def _host_spans(planes, prefixes) -> List[Tuple[float, float, str]]:
    spans = []
    for plane in planes:
        if not plane["name"].startswith("/host"):
            continue
        for line in plane["lines"]:
            for name, start, dur in line["events"]:
                if dur > 0 and name.startswith(prefixes):
                    spans.append((start, start + dur, name))
    return spans


def _covering(spans, starts, t: float) -> Optional[str]:
    """The annotation covering host time ``t`` (annotations come from one
    thread and do not overlap: the last one starting at or before
    ``t``)."""
    i = bisect.bisect_right(starts, t) - 1
    if i >= 0 and t < spans[i][1]:
        return spans[i][2]
    return None


def kind_of(annotation: Optional[str]) -> str:
    """``bench.decode:123:4`` -> ``bench.decode``."""
    return annotation.split(":")[0] if annotation else "host"


def reduce(trace: Dict[str, Any], window_s: float, *, top: int = 10,
           host_prefixes: Tuple[str, ...] = ("bench.",)) -> Summary:
    """Summarise a trace taken over ``window_s`` host seconds."""
    planes = trace["planes"]
    devices = [p for p in planes if p["name"].startswith("/device:")
               and (_line(p, OPS_LINE) or _line(p, MODULES_LINE))]
    if not devices:
        raise ValueError("the trace holds no device operations")
    spans = sorted(_host_spans(planes, host_prefixes))
    starts = [sp[0] for sp in spans]
    calls: List[Tuple[str, float, Optional[str]]] = []
    programs: Dict[str, List[float]] = collections.defaultdict(
        lambda: [0, 0.0])
    ops: Dict[str, float] = collections.defaultdict(float)
    gaps: List[Tuple[float, str]] = []
    busy_total = 0.0
    for dev in devices:
        modules = _line(dev, MODULES_LINE) or []
        for name, start, dur in modules:
            prog = program_name(name)
            p = programs[prog]
            p[0] += 1
            p[1] += dur * 1e-9
            calls.append((prog, dur * 1e-9,
                          _covering(spans, starts, start + dur / 2)))
        mod_iv = sorted((s, s + d, program_name(n)) for n, s, d in modules)
        mod_starts = [m[0] for m in mod_iv]
        op_events = _line(dev, OPS_LINE) or modules
        for name, start, dur in op_events:
            i = bisect.bisect_right(mod_starts, start) - 1
            owner = mod_iv[i][2] if i >= 0 and \
                start < mod_iv[i][1] else "?"
            ops[f"{owner}:{name}"] += dur * 1e-9
        busy = union([(s, s + d) for _, s, d in op_events])
        busy_total += sum(e - s for s, e in busy) * 1e-9
        for (_, a), (b, _) in zip(busy, busy[1:]):
            gaps.append(((b - a) * 1e-9,
                         kind_of(_covering(spans, starts, (a + b) / 2))))
    gaps.sort(key=lambda g: -g[0])
    return Summary(
        devices=len(devices), window_s=float(window_s),
        busy_s=busy_total / len(devices),
        programs={k: (int(v[0]), float(v[1])) for k, v in programs.items()},
        top_ops=sorted(ops.items(), key=lambda kv: -kv[1])[:top],
        idle_gaps=[(name, secs) for secs, name in gaps[:top]],
        calls=calls)
