"""From a profiler trace to numbers: the reduction every PR shares.

The JAX profiler writes ``<dir>/plugins/profile/<time>/*.xplane.pb``. On a TPU
(looked at by hand, PR 24) each chip is a plane ``/device:TPU:<n>`` whose
line ``XLA Ops`` holds one event per executed HLO instruction (the event's
name is the instruction's whole text; they never overlap on one chip), line
``XLA Modules`` one event per executed program and line ``Async XLA Ops`` the
copies and collectives in flight. ``TraceAnnotation`` spans of the host land
on plane ``/host:CPU``, line ``python``, on the same clock.

``load`` turns the file into plain lists; everything else works on those
lists, so it is checked on a small recorded trace without a chip
(``trace_check.py``, ``testdata/tpu_trace_small.json``).
"""

import glob
import os
import re
from typing import Dict, List, Tuple

Event = Tuple[str, float, float]  # name, start_s, duration_s

OPS_LINE = "XLA Ops"
MODULES_LINE = "XLA Modules"
SPAN_PREFIX = "chipbench/"


def find_xplane(trace_dir: str) -> str:
    paths = sorted(glob.glob(os.path.join(trace_dir, "plugins", "profile", "*", "*.xplane.pb")))
    if not paths:
        raise FileNotFoundError(f"no .xplane.pb under {trace_dir}")
    return paths[-1]


def load(path: str) -> Dict[str, object]:
    """``{"ops": {plane: [Event]}, "modules": {plane: [Event]}, "spans": [Event]}``."""
    import jax

    data = jax.profiler.ProfileData.from_file(path)
    ops: Dict[str, List[Event]] = {}
    modules: Dict[str, List[Event]] = {}
    spans: List[Event] = []
    for plane in data.planes:
        device = plane.name.startswith("/device:TPU:")
        host = plane.name.startswith("/host:")
        if not (device or host):
            continue
        for line in plane.lines:
            if device and line.name in (OPS_LINE, MODULES_LINE):
                target = ops if line.name == OPS_LINE else modules
                target.setdefault(plane.name, []).extend(
                    (e.name, e.start_ns * 1e-9, e.duration_ns * 1e-9) for e in line.events
                )
            elif host:
                spans.extend(
                    (e.name, e.start_ns * 1e-9, e.duration_ns * 1e-9)
                    for e in line.events
                    if e.name.startswith(SPAN_PREFIX)
                )
    return {"ops": ops, "modules": modules, "spans": spans}


def merged(events: List[Event]) -> List[Tuple[float, float]]:
    """Union of the events' intervals as sorted, disjoint (start, end)."""
    out: List[List[float]] = []
    for _, start, dur in sorted(events, key=lambda e: e[1]):
        end = start + dur
        if out and start <= out[-1][1]:
            out[-1][1] = max(out[-1][1], end)
        else:
            out.append([start, end])
    return [(a, b) for a, b in out]


def busy_seconds(ops: Dict[str, List[Event]]) -> float:
    """Seconds in which an operation ran on the device, averaged over chips."""
    if not ops:
        return 0.0
    per_chip = [sum(b - a for a, b in merged(events)) for events in ops.values()]
    return sum(per_chip) / len(per_chip)


def short_name(text: str) -> str:
    """``%fusion.58 = bf16[4,512,16384]{...} fusion(...)`` -> ``fusion.58 bf16[4,512,16384] fusion``."""
    head, _, rest = text.partition(" = ")
    if not rest:
        return text[:80]
    shape = rest.split("{", 1)[0].split(" ", 1)[0].strip("(")
    m = re.search(r"[\s)}]([a-z][a-z0-9\-]*)\(", " " + rest)
    return f"{head.lstrip('%')} {shape[:40]} {m.group(1) if m else ''}".strip()


def op_seconds(ops: Dict[str, List[Event]], pattern: str) -> float:
    """Summed device seconds of the events (operations or programs) whose
    text matches, averaged over chips."""
    rx = re.compile(pattern)
    if not ops:
        return 0.0
    return sum(d for events in ops.values() for n, _, d in events if rx.search(n)) / len(ops)


def top_ops(ops: Dict[str, List[Event]], n: int = 10) -> List[List[object]]:
    total: Dict[str, float] = {}
    for events in ops.values():
        for name, _, dur in events:
            key = short_name(name)
            total[key] = total.get(key, 0.0) + dur / len(ops)
    return [[k, v] for k, v in sorted(total.items(), key=lambda kv: -kv[1])[:n]]


def idle_gaps(ops: Dict[str, List[Event]], spans: List[Event], window: Tuple[float, float],
              n: int = 10) -> List[List[object]]:
    """Idle seconds of the first chip inside ``window``, summed by the
    innermost benchmark span the host was in when the gap began."""
    if not ops:
        return []
    events = ops[sorted(ops)[0]]
    busy = merged(events)
    gaps: List[Tuple[float, float]] = []
    cursor = window[0]
    for a, b in busy:
        if a > cursor:
            gaps.append((cursor, min(a, window[1])))
        cursor = max(cursor, b)
    if cursor < window[1]:
        gaps.append((cursor, window[1]))
    spans = sorted(spans, key=lambda s: s[2])  # shortest (innermost) first
    total: Dict[str, float] = {}
    for a, b in gaps:
        if b <= a:
            continue
        where = "outside benchmark spans"
        for name, start, dur in spans:
            if start <= a < start + dur:
                where = name
                break
        total[where] = total.get(where, 0.0) + (b - a)
    return [[k, v] for k, v in sorted(total.items(), key=lambda kv: -kv[1])[:n]]


def traced_window(ops: Dict[str, List[Event]], spans: List[Event]) -> Tuple[float, float]:
    """The stretch the numbers refer to: from the first to the last thing
    recorded, device operation or benchmark span."""
    starts = [s for events in ops.values() for _, s, _ in events] + [s for _, s, _ in spans]
    ends = [s + d for events in ops.values() for _, s, d in events] + [s + d for _, s, d in spans]
    return (min(starts), max(ends)) if starts else (0.0, 0.0)
