"""Metrics registry (counters / gauges / histograms) + throughput & MFU math.

Every metric key follows the repo-wide ``namespace/name`` convention
(enforced by ``scripts/check_metric_names.py``). The registry is a plain
in-process sink: the trainer merges ``snapshot()`` into its per-step stats
dict, so everything flows through the existing ``Tracker`` stream (JSONL /
TensorBoard / W&B) with no new backend.

MFU here is *measured*, not estimated: the FLOP numerator comes from XLA's
``cost_analysis()`` of the **exact compiled program** the trainer runs (the
same machinery as ``trlx_tpu/perf.py`` — see ``perf.lowered_costs``), joined
against the device-fenced step time from the span tracer. ``cost_analysis``
reports *per-device* flops, so MFU divides by the per-device peak directly.

Off-TPU (the CPU test meshes) a nominal ``DEFAULT_PEAK_FLOPS`` (1 TFLOP/s)
keeps ``throughput/mfu`` defined as a run-over-run *relative* index — never
a device's utilization. On a TPU the peak comes from :data:`TPU_PEAK_FLOPS`
by ``device_kind`` and a kind the table does not know is an error, not a
default; ``TRLX_TPU_PEAK_FLOPS`` (per device) overrides either.
"""

import os
import threading
from typing import Any, Dict, List, Optional

# bf16 peak per chip, keyed by a substring of ``device_kind`` — single source
# of truth (read through device_peak_flops). Sources: Google
# Cloud TPU documentation, system architecture pages per generation. A v5e
# reports ``device_kind == "TPU v5 lite"``.
TPU_PEAK_FLOPS = {
    "v4": 275e12,
    "v5e": 197e12,
    "v5 lite": 197e12,
    "v5p": 459e12,
    "v6e": 918e12,
}

# nominal per-device peak off-TPU (CPU test meshes): keeps throughput/mfu
# defined as a relative index rather than absent
DEFAULT_PEAK_FLOPS = 1e12


def device_peak_flops(device=None) -> float:
    """Per-device peak FLOP/s: ``TRLX_TPU_PEAK_FLOPS`` env override, else
    the TPU table by ``device_kind`` (an unknown TPU kind raises), else —
    off-TPU only — the nominal :data:`DEFAULT_PEAK_FLOPS`."""
    env = os.environ.get("TRLX_TPU_PEAK_FLOPS")
    if env:
        return float(env)
    if device is None:
        import jax

        device = jax.local_devices()[0]
    kind = getattr(device, "device_kind", "").lower()
    for key, val in TPU_PEAK_FLOPS.items():
        if key in kind:
            return val
    if getattr(device, "platform", None) == "tpu":
        raise ValueError(
            f"no peak FLOP/s known for TPU device_kind {kind!r}: add it to "
            "TPU_PEAK_FLOPS (with its source) or set TRLX_TPU_PEAK_FLOPS"
        )
    return DEFAULT_PEAK_FLOPS


def mfu(flops_per_device: float, step_time_s: float, peak_flops_per_device: float) -> float:
    """Model FLOP utilization of one device for one measured step.

    ``flops_per_device`` must be XLA ``cost_analysis`` flops (already
    per-device under SPMD), ``step_time_s`` a device-fenced wall time.
    """
    if step_time_s <= 0 or peak_flops_per_device <= 0:
        return 0.0
    return flops_per_device / step_time_s / peak_flops_per_device


class MetricsRegistry:
    """Thread-safe counters / gauges / histograms with a flat snapshot.

    - counter: monotonically accumulates (``recompile/train_step``);
    - gauge: last-write-wins (``memory/device_bytes_in_use``);
    - histogram: per-window observations, summarized at snapshot as
      ``name_mean`` / ``name_max`` / ``name_count`` and reset.
    """

    def __init__(self):
        self._lock = threading.Lock()
        # resilience counters inc() from pipeline worker threads while the
        # learn loop snapshots: all mutations take the lock (enforced by
        # graftlint's lock-discipline pass, docs/STATIC_ANALYSIS.md)
        self._counters: Dict[str, float] = {}  # guarded-by: _lock
        self._gauges: Dict[str, float] = {}  # guarded-by: _lock
        self._hists: Dict[str, List[float]] = {}  # guarded-by: _lock
        # update listeners (the crash flight recorder): called on every
        # inc/set_gauge so resilience counters and cluster gauges land in
        # the forensic ring as they happen
        self._listeners: List[Any] = []  # guarded-by: _lock

    def add_listener(self, fn) -> None:
        """Subscribe to every counter/gauge write as ``fn(op, name, value)``
        — the crash flight recorder's tap."""
        with self._lock:
            self._listeners.append(fn)

    def _notify(self, op: str, name: str, value: float) -> None:
        # listeners run outside the lock and are never allowed to break
        # metric recording
        with self._lock:
            listeners = list(self._listeners)
        for fn in listeners:
            try:
                fn(op, name, value)
            except Exception:  # pragma: no cover - defensive
                pass

    def inc(self, name: str, value: float = 1.0) -> float:
        with self._lock:
            self._counters[name] = self._counters.get(name, 0.0) + value
            total = self._counters[name]
        self._notify("inc", name, total)
        return total

    def set_gauge(self, name: str, value: float) -> None:
        with self._lock:
            self._gauges[name] = float(value)
        self._notify("gauge", name, float(value))

    def observe(self, name: str, value: float) -> None:
        with self._lock:
            self._hists.setdefault(name, []).append(float(value))

    def counter(self, name: str) -> float:
        with self._lock:
            return self._counters.get(name, 0.0)

    def snapshot(self, reset_histograms: bool = True) -> Dict[str, float]:
        """Flat ``namespace/name`` → value dict for the tracker stream."""
        with self._lock:
            out: Dict[str, float] = dict(self._counters)
            out.update(self._gauges)
            for name, values in self._hists.items():
                if not values:
                    continue
                out[f"{name}_mean"] = sum(values) / len(values)
                out[f"{name}_max"] = max(values)
                out[f"{name}_count"] = float(len(values))
            if reset_histograms:
                self._hists = {}
            return out


class ThroughputMeter:
    """Derives per-step throughput stats from fenced step times.

    ``step_stats`` returns the canonical keys the tracker stream carries:
    ``throughput/tokens_per_sec``, ``throughput/samples_per_sec``, and —
    when a program FLOP count is known — ``throughput/mfu`` plus
    ``throughput/flops_per_sec_per_device``. Running totals fold in so a
    final ``summary()`` reports whole-run averages.
    """

    def __init__(self, peak_flops_per_device: Optional[float] = None):
        self._peak = peak_flops_per_device
        self.total_time = 0.0
        self.total_tokens = 0
        self.total_samples = 0

    @property
    def peak(self) -> float:
        if self._peak is None:
            self._peak = device_peak_flops()
        return self._peak

    def step_stats(
        self,
        step_time_s: float,
        tokens: int = 0,
        samples: int = 0,
        flops_per_device: Optional[float] = None,
    ) -> Dict[str, float]:
        stats: Dict[str, float] = {}
        if step_time_s <= 0:
            return stats
        self.total_time += step_time_s
        self.total_tokens += tokens
        self.total_samples += samples
        if tokens:
            stats["throughput/tokens_per_sec"] = tokens / step_time_s
        if samples:
            stats["throughput/samples_per_sec"] = samples / step_time_s
        if flops_per_device is not None and flops_per_device > 0:
            stats["throughput/flops_per_sec_per_device"] = (
                flops_per_device / step_time_s
            )
            stats["throughput/mfu"] = mfu(flops_per_device, step_time_s, self.peak)
        return stats

    def summary(self) -> Dict[str, float]:
        if self.total_time <= 0:
            return {}
        out = {}
        if self.total_tokens:
            out["throughput/tokens_per_sec_avg"] = self.total_tokens / self.total_time
        if self.total_samples:
            out["throughput/samples_per_sec_avg"] = (
                self.total_samples / self.total_time
            )
        return out


def train_step_flops(jitted_fn, *args: Any) -> Optional[float]:
    """Per-device FLOPs of the exact compiled train step, via the same XLA
    ``cost_analysis`` path as ``trlx_tpu/perf.py``.

    Lowers ``jitted_fn`` with abstract (shape/dtype/sharding) twins of the
    live arguments (state, batch, and any trailing scalars) — no arrays are
    touched, and with the persistent compile cache on, the AOT compile
    dedupes against the call-path executable. Returns ``None`` (never
    raises) when the backend has no cost model or lowering fails; disable
    entirely with ``TRLX_TPU_MFU=0``.
    """
    if os.environ.get("TRLX_TPU_MFU", "1") == "0":
        return None
    try:
        import jax

        from trlx_tpu.perf import lowered_costs

        def abstract(tree):
            return jax.tree_util.tree_map(
                lambda x: jax.ShapeDtypeStruct(
                    x.shape, x.dtype, sharding=getattr(x, "sharding", None)
                ),
                tree,
            )

        costs = lowered_costs(jitted_fn.lower(*(abstract(a) for a in args)))
        flops = costs.get("flops", -1.0)
        return flops if flops > 0 else None
    except Exception:
        return None
