"""Check the trace reduction on a small recorded TPU trace, on the CPU.

    python3 -m chipbench.trace_check

``testdata/tpu_trace_small.json`` is one step of a two-layer GPT-J-width
forward and backward, recorded on a v5e by PR 24 (plane, line, name, start
and duration in nanoseconds). Busy time, idle gaps and per-operation sums
are recomputed here by brute force (a nanosecond-free sweep over sorted
endpoints) and compared with ``trace.py``.
"""

import json
import os
import sys

from chipbench import trace


def load_recorded():
    path = os.path.join(os.path.dirname(os.path.abspath(__file__)), "testdata",
                        "tpu_trace_small.json")
    with open(path) as f:
        rows = json.load(f)["events"]
    ops, modules, spans = {}, {}, []
    for plane, line, name, start, dur in rows:
        ev = (name, start * 1e-9, dur * 1e-9)
        if line == trace.OPS_LINE:
            ops.setdefault(plane, []).append(ev)
        elif line == trace.MODULES_LINE:
            modules.setdefault(plane, []).append(ev)
        elif name.startswith(trace.SPAN_PREFIX):
            spans.append(ev)
    return ops, modules, spans


def brute_busy(events):
    """Endpoint sweep: count of open intervals > 0."""
    points = sorted([(s, 1) for _, s, _ in events] + [(s + d, -1) for _, s, d in events])
    open_, busy, last = 0, 0.0, None
    for t, step in points:
        if open_ > 0:
            busy += t - last
        open_ += step
        last = t
    return busy


def main() -> int:
    ops, modules, spans = load_recorded()
    events = next(iter(ops.values()))
    checks = []
    busy = trace.busy_seconds(ops)
    checks.append(("busy", busy, brute_busy(events)))
    window = trace.traced_window(ops, spans)
    gaps = trace.idle_gaps(ops, spans, window)
    checks.append(("busy+idle=window", busy + sum(v for _, v in gaps), window[1] - window[0]))
    flash = trace.op_seconds(ops, 'custom_call_target="tpu_custom_call"')
    by_hand = sum(d for n, _, d in events if "tpu_custom_call" in n)
    checks.append(("flash sum", flash, by_hand))
    checks.append(("all ops <= module time", float(sum(d for _, _, d in events) <=
                   trace.op_seconds(modules, ".") + 1e-9), 1.0))
    top = trace.top_ops(ops, 3)
    ok = True
    for name, got, want in checks:
        good = abs(got - want) <= 1e-9 + 1e-9 * abs(want)
        ok &= good
        print(f"{'ok  ' if good else 'FAIL'} {name}: {got!r} vs {want!r}")
    print("top ops:", top)
    print("idle by span:", gaps)
    print("spans:", len(spans), "flash events:", sum("tpu_custom_call" in n for n, _, _ in events))
    ok &= flash > 0 and len(top) == 3 and len(spans) > 0
    return 0 if ok else 1


if __name__ == "__main__":
    sys.exit(main())
