"""Per-layer metrics, as data.

Each metric is a file ``chipbench/layer_metrics/<name>.json``: its layer, its
unit, the end-to-end metric it should move, and which of the few reducers
below reads it from where. A later PR adds a metric over a new span, counter
or trace pattern as a new file and a new entry of ``BENCHMARK.json``'s
``per_layer``. A reducer that finds nothing to read returns ``None`` and the
metric is left out of the line.

Reducers (``"reducer"`` in the file):

- ``stat_share``: sum of tracker stat ``key`` over the window's whole cycles
  (``"where": "collection"`` or ``"steps"``), over the cycles' wall time, %.
- ``stat_median``: median of ``key`` over the window's step records, times
  ``scale``.
- ``stat_mean``: mean of ``key`` over the window's collection records, times
  ``scale``.
- ``counter``: programs compiled in the window (jax's compile events plus
  the program's ``recompile/*`` counters).
- ``required_flops_share``: FLOPs the optimizer steps of a cycle require,
  from the model as it is (``flops.py``: every layer's own tree, the mask,
  the family's ``costs/<family>.py`` where it brought one), over their
  fenced time and the chip's peak, %. A tree the count cannot read leaves
  the metric out with the reason printed.
- ``trace_op_roofline``: the floor of the operations matching ``pattern``
  over their summed device time in the traced cycle, %. The floor is the
  REQUIRED work of that cycle, which the function ``costs`` names
  (``flops.py``, or the family's file) returns as phases of operations and
  bytes: a phase's floor is ``max(flops / peak FLOP/s, bytes / peak
  bytes/s)``, the cycle's their sum. No matching operation, no phase or an
  unknown chip: nothing.
- ``trace_op_sum``: summed device time of operations matching ``pattern`` in
  the traced stretch (one cycle), times ``scale``.
- ``trace_module_share``: device time of programs (``XLA Modules``) matching
  ``pattern`` over the traced stretch, %.
- ``trace_op_share``: device time of operations matching ``pattern`` over
  the traced stretch, %.
- ``trace_idle``: 1 - device busy over the traced stretch, %.
"""

import glob
import json
import os
import statistics
from typing import Any, Dict, Optional

from chipbench import flops, job, trace

HERE = os.path.dirname(os.path.abspath(__file__))


def metric_files() -> Dict[str, Dict[str, Any]]:
    out = {}
    for path in sorted(glob.glob(os.path.join(HERE, "layer_metrics", "*.json"))):
        with open(path) as f:
            spec = json.load(f)
        out[spec["name"]] = spec
    return out


def _cycle_seconds(h) -> float:
    return sum(c["end"] - c["start"] for c in h.cycles)


def _model(h) -> "flops.Model":
    if getattr(h, "flops_model", None) is None:
        h.flops_model = flops.Model(h.trainer, h.config_file.get("family"))
    return h.flops_model


def _required_share(spec: Dict[str, Any], h, tr, peak_row, chips: int) -> Optional[float]:
    """The two readers of ``flops.py``: a share of the chip's peak over the
    steps' fenced time, and a share of a kernel's roofline over its device
    time in the traced cycle (``h.cycles[0]``: the profiler runs over the
    first whole cycle of the window)."""
    model = _model(h)
    if spec["reducer"] == "required_flops_share":
        need = sum(flops.learn_flops_of_cycle(h.trainer, c, model=model) for c in h.cycles)
        spent = sum(s["time/train_step"] for c in h.cycles for s in c["steps"])
        if peak_row is None:
            return None
        return 100.0 * need / spent / (peak_row["bf16_flops_per_s"] * chips)
    phases = flops.kernel_costs(spec["costs"], model)(model, h.cycles[0])
    if peak_row is None or tr is None or not tr["ops"] or not phases:
        return None
    spent = trace.op_seconds(tr["ops"], spec["pattern"])
    if spent <= 0.0:
        return None
    floor = flops.floor_seconds(phases, peak_row, chips)
    print(json.dumps({"roofline": spec["name"], "device_s": spent, "floor_s": floor,
                      "phases": phases}), flush=True)
    return 100.0 * floor / spent


def reduce_one(spec: Dict[str, Any], h, tr: Optional[Dict[str, Any]], peak_row, chips: int
               ) -> Optional[float]:
    kind = spec["reducer"]
    scale = float(spec.get("scale", 1.0))
    if kind == "stat_share":
        if spec["where"] == "collection":
            vals = [c["collection"].get(spec["key"]) for c in h.cycles]
        else:
            vals = [s.get(spec["key"]) for c in h.cycles for s in c["steps"]]
        vals = [v for v in vals if v is not None]
        return 100.0 * sum(vals) / _cycle_seconds(h) if vals else None
    if kind == "stat_median":
        vals = [s[spec["key"]] for c in h.cycles for s in c["steps"] if spec["key"] in s]
        return scale * statistics.median(vals) if vals else None
    if kind == "stat_mean":
        vals = [c["collection"][spec["key"]] for c in h.cycles if spec["key"] in c["collection"]]
        return scale * statistics.fmean(vals) if vals else None
    if kind == "counter":
        return float(h.check_values.get("compiles_in_window", 0)
                     + sum(h.check_values.get("no_recompile_detail", {}).values()))
    if kind == "required_flops_share" or kind == "trace_op_roofline":
        try:  # counted on the CPU walk too, where there is no peak to divide by
            return _required_share(spec, h, tr, peak_row, chips)
        except flops.Uncountable as e:
            print(json.dumps({"metric_left_out": spec["name"], "reason": str(e)}), flush=True)
            return None
    if tr is None or not tr["ops"]:
        return None
    window = tr["window"][1] - tr["window"][0]
    if kind == "trace_op_sum":
        return scale * trace.op_seconds(tr["ops"], spec["pattern"])
    if kind == "trace_op_share":
        return 100.0 * trace.op_seconds(tr["ops"], spec["pattern"]) / window
    if kind == "trace_module_share":
        return 100.0 * trace.op_seconds(tr["modules"], spec["pattern"]) / window
    if kind == "trace_idle":
        return 100.0 * (1.0 - trace.busy_seconds(tr["ops"]) / window)
    raise ValueError(f"layer_metrics/{spec['name']}.json: unknown reducer {kind!r}")


def per_layer(h, cell, peak_row, chips: int):
    """The ``--trace 1`` line's ``metrics``, ``breakdown`` and the ``busy_s`` /
    ``window_s`` of ``device``."""
    tr = None
    if getattr(h, "trace_dir", None):
        tr = trace.load(trace.find_xplane(h.trace_dir))
        tr["window"] = trace.traced_window(tr["ops"], tr["spans"])
    declared = {m["name"]: m for m in job.load_benchmark()["per_layer"]}
    files = metric_files()
    metrics = {}
    for name, entry in declared.items():
        if "workloads" in entry and cell["name"] not in entry["workloads"]:
            continue
        spec = files[name]
        value = reduce_one(spec, h, tr, peak_row, chips)
        if value is not None:
            metrics[name] = {"value": value, "unit": spec["unit"]}
    breakdown = {"device_ops": [], "idle_gaps": []}
    busy = {"busy_s": 0.0, "window_s": 0.0}
    if tr is not None and tr["ops"]:
        breakdown = {
            "device_ops": trace.top_ops(tr["ops"], 10),
            "idle_gaps": trace.idle_gaps(tr["ops"], tr["spans"], tr["window"], 10),
        }
        busy = {"busy_s": trace.busy_seconds(tr["ops"]),
                "window_s": tr["window"][1] - tr["window"][0]}
    return metrics, breakdown, busy
