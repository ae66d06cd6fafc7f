"""Watchdogs: silent-recompile detection and device-memory gauging.

Two failure modes are invisible until a pod run dies:

- **steady-state recompiles** — a shape/dtype drift (unpadded batch, a new
  gen-kwarg combination) makes a supposedly-warm jitted program retrace
  every step, turning a 100ms step into a multi-second one with no error;
- **HBM growth** — a leaked buffer or an unexpectedly replicated tree grows
  device memory until an OOM kills the run hours in.

:class:`RecompileWatchdog` tracks each registered jitted callable's compile
cache (``_cache_size()`` where the jit wrapper exposes it, an argument
shape-signature set otherwise) and logs a warning — plus a
``recompile/<program>`` counter — whenever a program that already compiled
once compiles *again*, unless the caller planned the shape: a pad policy
that feeds one program a fixed set of shapes (the PPO learner's ladder of
widths) names the shape of each call, and the first compile of each planned
shape is as expected as the program's first. :class:`DeviceMemoryGauge` reads
``device.memory_stats()`` where the backend provides it (TPU/GPU), falling
back to host RSS on CPU, and warns when usage crosses a fraction of the
device limit.
"""

from typing import Any, Callable, Dict, Optional

from trlx_tpu.utils import logging

logger = logging.get_logger(__name__)


def _cache_size(fn: Callable) -> Optional[int]:
    """Compile-cache entry count of a ``jax.jit`` wrapper, if exposed."""
    probe = getattr(fn, "_cache_size", None)
    if probe is None:
        return None
    try:
        return int(probe())
    except Exception:
        return None


def _signature(args: Any) -> tuple:
    import jax

    return tuple(
        (getattr(leaf, "shape", None), str(getattr(leaf, "dtype", type(leaf))))
        for leaf in jax.tree_util.tree_leaves(args)
    )


class RecompileWatchdog:
    """Warns when a warm jitted program compiles again.

    The *first* compile of a program is expected and silent; every
    subsequent cache growth for the same program name is counted
    (``recompile/<name>``) and logged — one warning per event, with a
    rate-limit so a pathological per-step retrace doesn't flood the log.
    A call that names a ``planned`` shape (``observe``) is allowed that
    shape's first compile too; a compile at a planned shape already seen
    counts like any other.
    """

    def __init__(self, metrics=None, max_warnings: int = 10):
        self.metrics = metrics
        self.max_warnings = max_warnings
        # all bookkeeping is per (name, id(fn)): several distinct jitted
        # programs may share one logical name (e.g. the eval-config and
        # experience-config "generate" fns), and a second program's *first*
        # compile must not be reported as a retrace of the first
        self._cache_sizes: Dict[tuple, int] = {}  # key -> last seen size
        self._signatures: Dict[tuple, set] = {}  # key -> seen arg signatures
        self._compiles: Dict[tuple, int] = {}  # key -> total compiles seen
        self._planned: Dict[tuple, set] = {}  # key -> planned shapes seen
        self._warnings = 0

    def observe(
        self, name: str, fn: Callable, args: Any = None, planned: Any = None
    ) -> int:
        """Record one call of ``fn`` under program ``name``; returns the
        number of *excess* (post-warmup) compiles seen for this fn so far.
        ``planned`` (hashable, or ``None``) names the shape of this call where
        the caller's pad policy set it out beforehand."""
        key = (name, id(fn))
        size = _cache_size(fn)
        if size is not None:
            prev = self._cache_sizes.get(key)
            self._cache_sizes[key] = size
            new = size - prev if prev is not None else size
        elif args is not None:  # fallback: shape-signature tracking
            seen = self._signatures.setdefault(key, set())
            sig = _signature(args)
            new = 0 if sig in seen else 1
            seen.add(sig)
        else:
            return 0
        if planned is not None:
            seen = self._planned.setdefault(key, set())
            # the program's own first compile is free whatever its shape:
            # only a later first sight of a planned shape needs the allowance
            if planned not in seen and new > 0 and self._compiles.get(key, 0) > 0:
                new -= 1
            seen.add(planned)
        total = self._compiles.get(key, 0) + new
        if new <= 0:
            return max(total - 1, 0)
        self._compiles[key] = total
        if total > 1:
            newly_excess = min(new, total - 1)
            if self.metrics is not None:
                self.metrics.inc(f"recompile/{name}", newly_excess)
            if self._warnings < self.max_warnings:
                self._warnings += 1
                logger.warning(
                    "recompile watchdog: program '%s' retraced (compile #%d) — "
                    "a warm program recompiling usually means a shape/dtype "
                    "drift in its inputs; every retrace stalls the step for a "
                    "full XLA compile",
                    name,
                    total,
                )
        return max(total - 1, 0)

    def excess_compiles(self, name: str) -> int:
        """Compiles beyond each program's expected first one, summed over
        every fn observed under ``name``."""
        return sum(
            max(total - 1, 0)
            for (prog, _fn_id), total in self._compiles.items()
            if prog == name
        )


class DeviceMemoryGauge:
    """Per-step device-memory stats with graceful CPU fallback.

    ``collect()`` returns gauge metrics (also mirrored into a registry when
    one is attached): ``memory/device_bytes_in_use`` / ``_peak_bytes`` /
    ``_limit_bytes`` (max over local devices) when the backend reports
    ``memory_stats()``, plus ``memory/host_rss_bytes`` always. Crossing
    ``warn_frac`` of the device limit logs one warning per run.
    """

    def __init__(self, metrics=None, warn_frac: float = 0.92):
        self.metrics = metrics
        self.warn_frac = warn_frac
        self._warned = False

    @staticmethod
    def _host_rss_bytes() -> Optional[float]:
        try:
            import resource
            import sys

            rss = resource.getrusage(resource.RUSAGE_SELF).ru_maxrss
            # ru_maxrss is KiB on Linux, bytes on macOS
            return float(rss) * (1.0 if sys.platform == "darwin" else 1024.0)
        except Exception:
            return None

    def collect(self) -> Dict[str, float]:
        out: Dict[str, float] = {}
        in_use = peak = limit = None
        try:
            import jax

            for dev in jax.local_devices():
                ms = dev.memory_stats() if hasattr(dev, "memory_stats") else None
                if not ms:
                    continue
                use = ms.get("bytes_in_use")
                if use is not None:
                    in_use = max(in_use or 0.0, float(use))
                pk = ms.get("peak_bytes_in_use")
                if pk is not None:
                    peak = max(peak or 0.0, float(pk))
                lim = ms.get("bytes_limit") or ms.get("bytes_reservable_limit")
                if lim:
                    limit = max(limit or 0.0, float(lim))
        except Exception:
            pass
        if in_use is not None:
            out["memory/device_bytes_in_use"] = in_use
        if peak is not None:
            out["memory/device_peak_bytes"] = peak
        if limit is not None:
            out["memory/device_limit_bytes"] = limit
        rss = self._host_rss_bytes()
        if rss is not None:
            out["memory/host_rss_bytes"] = rss
        if (
            not self._warned
            and in_use is not None
            and limit
            and in_use / limit > self.warn_frac
        ):
            self._warned = True
            logger.warning(
                "memory watchdog: device memory at %.1f%% of limit "
                "(%.2f / %.2f GiB) — the next allocation spike may OOM",
                100.0 * in_use / limit,
                in_use / 2**30,
                limit / 2**30,
            )
        if self.metrics is not None:
            for k, v in out.items():
                self.metrics.set_gauge(k, v)
        return out
