"""Idle seconds of the chip by the program's own spans. Not run by the driver.

    python3 -m chipbench.host_gaps <trace_dir>

The program mirrors every span of its tracer as a
``jax.profiler.TraceAnnotation("trlx/<name>")`` (``observability/tracing.py``),
so a trace taken by ``run.py --trace 1`` (``.chipbench_out/trace``) or by
``TRLX_TPU_PROFILE`` holds them on ``/host:CPU`` beside the chip's ``XLA Ops``.
This reads those events and prints, over the same stretch and the same gaps
as ``trace.idle_gaps``:

- idle seconds by the innermost program span at each moment of a gap (a gap
  that spans several spans is cut at their boundaries), and beside it the
  ledger's rule, the span the host was in when the gap BEGAN
  (``trace.idle_gaps`` itself, given the program's spans). Under that rule a
  gap between two train steps goes to ``trlx/train_step``: it begins while
  the host still waits inside the span's fence;
- the share that lands on a span below ``collect/experience`` or in the
  learn loop, and not on a root or outside every span;
- whether every ``trlx/train_step`` event contains the start of one
  train-step program and ends no earlier than it (the fence is inside);
- how many program spans the stretch holds.
"""

import bisect
import sys
from typing import Dict, List, Tuple

from chipbench import trace

PREFIX = "trlx/"
OUTSIDE = "outside program spans"
# spans that only group others: idle that lands on them is not attributed
ROOTS = (PREFIX + "collect/experience", PREFIX + "learn/post_epoch")
TRAIN_STEP_SPAN = PREFIX + "train_step"
TRAIN_STEP_MODULE = "jit_train_step("


def program_spans(path: str) -> List[trace.Event]:
    """The ``trlx/`` host events of an ``.xplane.pb``, every thread's. A span
    that says which ``stage`` of its work it is reads ``name[stage]``."""
    import jax

    data = jax.profiler.ProfileData.from_file(path)
    out = []
    for plane in data.planes:
        if not plane.name.startswith("/host:"):
            continue
        for line in plane.lines:
            for e in line.events:
                if e.name.startswith(PREFIX):
                    stage = dict(e.stats).get("stage")
                    name = f"{e.name}[{stage}]" if stage else e.name
                    out.append((name, e.start_ns * 1e-9, e.duration_ns * 1e-9))
    return out


def innermost_timeline(spans: List[trace.Event]) -> List[Tuple[float, float, str]]:
    """Disjoint ``(start, end, name)`` pieces: at each moment the shortest
    span open at it (``idle_gaps``'s notion of innermost), on any thread."""
    cuts = sorted({t for _, s, d in spans for t in (s, s + d)})
    by_length = sorted(spans, key=lambda s: s[2])
    out = []
    for a, b in zip(cuts, cuts[1:]):
        mid = 0.5 * (a + b)
        name = next((n for n, s, d in by_length if s <= mid < s + d), None)
        if name is not None:
            out.append((a, b, name))
    return out


def device_gaps(ops: Dict[str, List[trace.Event]], window: Tuple[float, float]
                ) -> List[Tuple[float, float]]:
    """Idle stretches of the first chip inside ``window``: the complement of
    ``trace.merged``, as ``trace.idle_gaps`` takes it."""
    gaps, cursor = [], window[0]
    for a, b in trace.merged(ops[sorted(ops)[0]]):
        if a > cursor:
            gaps.append((cursor, min(a, window[1])))
        cursor = max(cursor, b)
    if cursor < window[1]:
        gaps.append((cursor, window[1]))
    return [(a, b) for a, b in gaps if b > a]


def idle_by_slice(gaps, timeline) -> Dict[str, float]:
    starts = [a for a, _, _ in timeline]
    total: Dict[str, float] = {}
    for a, b in gaps:
        covered = 0.0
        i = max(bisect.bisect_right(starts, a) - 1, 0)
        while i < len(timeline) and timeline[i][0] < b:
            lo, hi = max(a, timeline[i][0]), min(b, timeline[i][1])
            if hi > lo:
                total[timeline[i][2]] = total.get(timeline[i][2], 0.0) + hi - lo
                covered += hi - lo
            i += 1
        if b - a - covered > 0:
            total[OUTSIDE] = total.get(OUTSIDE, 0.0) + b - a - covered
    return total


def train_step_fences(spans, modules) -> Tuple[int, int]:
    """``(sound, all)`` over the ``trlx/train_step`` events: sound when the
    event contains the start of exactly one train-step program and ends no
    earlier than that program ends."""
    programs = [(s, s + d) for events in modules.values() for n, s, d in events
                if TRAIN_STEP_MODULE in n]
    steps = [(s, s + d) for n, s, d in spans if n == TRAIN_STEP_SPAN]
    sound = 0
    for a, b in steps:
        inside = [(ps, pe) for ps, pe in programs if a <= ps < b]
        sound += len(inside) == 1 and inside[0][1] <= b
    return sound, len(steps)


def report(trace_dir: str) -> Dict[str, object]:
    path = trace.find_xplane(trace_dir)
    tr = trace.load(path)
    spans = program_spans(path)
    if not tr["ops"]:
        raise SystemExit(f"{path}: no device operations (not a trace of a chip)")
    window = trace.traced_window(tr["ops"], tr["spans"])
    inside = [s for s in spans if s[1] < window[1] and s[1] + s[2] > window[0]]
    gaps = device_gaps(tr["ops"], window)
    sliced = idle_by_slice(gaps, innermost_timeline(inside))
    idle = sum(b - a for a, b in gaps)
    named = sum(v for k, v in sliced.items() if k != OUTSIDE and k not in ROOTS)
    by_start = {OUTSIDE if k == "outside benchmark spans" else k: v
                for k, v in trace.idle_gaps(tr["ops"], inside, window, 1000)}
    sound, steps = train_step_fences(inside, tr["modules"])
    return {
        "window_s": window[1] - window[0], "idle_s": idle, "named_s": named,
        "by_slice": sorted(sliced.items(), key=lambda kv: -kv[1]),
        "by_start": by_start, "program_spans": len(inside),
        "train_step_fences": [sound, steps],
    }


def main(argv=None) -> int:
    argv = sys.argv[1:] if argv is None else argv
    if len(argv) != 1:
        print(__doc__)
        return 2
    r = report(argv[0])
    print(f"traced stretch {r['window_s']:.4f} s, chip idle {r['idle_s']:.4f} s, "
          f"{r['program_spans']} program spans")
    print(f"{'innermost program span':44s} {'idle s':>9s} {'share':>7s} {'by gap start':>13s}")
    sliced = dict(r["by_slice"])
    for name in list(sliced) + [k for k in r["by_start"] if k not in sliced]:
        v = sliced.get(name, 0.0)
        print(f"{name:44s} {v:9.4f} {100 * v / r['idle_s']:6.1f}% "
              f"{r['by_start'].get(name, 0.0):13.4f}")
    print(f"on a span below collect/experience or in the learn loop: "
          f"{r['named_s']:.4f} s, {100 * r['named_s'] / r['idle_s']:.1f}% of idle")
    sound, steps = r["train_step_fences"]
    print(f"trlx/train_step events holding one {TRAIN_STEP_MODULE}..) from its start "
          f"to past its end: {sound} of {steps}")
    return 0


if __name__ == "__main__":
    sys.exit(main())
