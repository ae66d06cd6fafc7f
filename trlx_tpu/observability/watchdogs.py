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

from typing import Any, Callable, Dict, NamedTuple, Optional

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


class DeviceMemory(NamedTuple):
    """The allocator's bytes, each the largest over the local devices;
    ``None`` where the backend does not report it (the CPU reports nothing).
    ``reserved`` is the region a TPU's runtime keeps at the bottom of memory
    for running programs' temporaries (``peak_bytes_reserved``): sized by the
    largest program it has loaded, shared by all of them, and NOT in
    ``in_use`` or ``peak``: what is free is limit - in use - reserved. A
    program that cannot get its region stops with ``RESOURCE_EXHAUSTED: Error
    loading program ...: Attempting to reserve ... at the bottom of memory``;
    the runtime shrinks the region only by unloading programs under pressure,
    so the largest it has been is what a job that is not to reload holds."""

    in_use: Optional[float] = None
    peak: Optional[float] = None
    limit: Optional[float] = None
    reserved: Optional[float] = None


_STATS = {"in_use": ("bytes_in_use",), "peak": ("peak_bytes_in_use",),
          "limit": ("bytes_limit", "bytes_reservable_limit"), "reserved": ("peak_bytes_reserved", "bytes_reserved")}


_KEYS = {"in_use": "memory/device_bytes_in_use", "peak": "memory/device_peak_bytes",
         "limit": "memory/device_limit_bytes", "reserved": "memory/device_reserved_bytes"}


def read_device_memory() -> DeviceMemory:
    """One ``memory_stats()`` call a local device on the host, no device work."""
    out: Dict[str, float] = {}
    try:
        import jax

        for dev in jax.local_devices():
            ms = dev.memory_stats() if hasattr(dev, "memory_stats") else None
            if not ms:
                continue
            for field, names in _STATS.items():
                value = next((ms[n] for n in names if ms.get(n)), ms.get(names[0]))
                if value is not None:
                    out[field] = max(out.get(field, 0.0), float(value))
    except Exception:
        pass
    return DeviceMemory(**out)


def shard_bytes(tree: Any) -> int:
    """Bytes of ``tree`` on the fullest local device: every leaf's largest
    shard, summed. Host arithmetic over shapes and shardings (abstract leaves
    count too), no device call."""
    import jax
    import numpy as np

    total = 0
    for leaf in jax.tree_util.tree_leaves(tree):
        shape, dtype = getattr(leaf, "shape", None), getattr(leaf, "dtype", None)
        if shape is None or dtype is None:
            continue
        sharding = getattr(leaf, "sharding", None)
        try:
            shape = sharding.shard_shape(tuple(shape)) if sharding is not None else shape
        except Exception:  # a sharding that does not divide the leaf: count it whole
            pass
        total += int(np.prod(shape, dtype=np.int64)) * np.dtype(dtype).itemsize
    return total


class DeviceMemoryGauge:
    """Per-step device-memory stats with graceful CPU fallback, and the
    account of what the allocator's numbers are made of.

    ``collect()`` returns gauge metrics (also mirrored into a registry when
    one is attached): ``memory/device_bytes_in_use`` / ``_peak_bytes`` /
    ``_limit_bytes`` / ``_reserved_bytes`` (max over local devices) when the
    backend reports ``memory_stats()``, plus ``memory/host_rss_bytes`` always.
    Crossing ``warn_frac`` of the device limit logs one warning per run.

    **The account** (docs/OBSERVABILITY.md "The memory account"). *Need*:
    ``memory/need_bytes`` = the allocator's peak + the region the runtime
    reserves for running programs' temporaries (:class:`DeviceMemory`), which
    the peak does NOT hold: both from the one read ``collect()`` makes anyway,
    absent where the allocator reports no reservation. (Ballast runs showed
    that the reservation is what a TPU holds on top of the reading, to the
    MiB, and that the compiler's ``temp_size_in_bytes`` is not: PERF.md
    section 6, PR 51.) The rest says what the reading is made of and is absent
    for a job whose programs are plain ``jax.jit`` (``collect(programs=None)``).
    *State*: what the trainer knows it holds (:meth:`note_state`). *Code*, and
    the compiler's largest temporaries as the planning estimate of the
    reservation: the rows of the job's executables
    (``utils/programs.py::ProgramStore.account``). *Untracked*: in use less
    state less code after a step's fence. ``read`` is the one seam (tests set
    it: the CPU reports nothing)."""

    def __init__(self, metrics=None, warn_frac: float = 0.92):
        self.metrics = metrics
        self.warn_frac = warn_frac
        self._warned = False
        self.read: Callable[[], DeviceMemory] = read_device_memory
        self._state: Dict[str, float] = {}
        self._last: Dict[str, float] = {}  # the newest collect(), for the log line
        self._programs: Optional[Dict[str, Any]] = None  # and the rows it was given
        self._logged = 0.0  # the need of the newest log line

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

    # -- the account ----------------------------------------------------------

    def note_state(self, params: Any, opt_state: Any, ref: Any) -> None:
        """What is resident by what the trainer knows it holds: parameters
        (a value head among them), optimizer state, the frozen reference (or
        its hydra branch). Once when learning is prepared, again when the tree
        changes (a restore)."""
        self._state = {
            "memory/params_bytes": float(shard_bytes(params)),
            "memory/opt_state_bytes": float(shard_bytes(opt_state)),
            "memory/ref_bytes": float(shard_bytes(ref)),
        }
        self._state["memory/state_bytes"] = sum(self._state.values())

    def _account(self, now: DeviceMemory, programs: Optional[Dict[str, Any]]) -> Dict[str, float]:
        out: Dict[str, float] = {}
        if now.peak is not None and now.reserved:
            out["memory/need_bytes"] = now.peak + now.reserved
        if programs is not None:
            out.update(self._state)
            out["memory/code_bytes"] = float(programs["code"])
            out["memory/programs_resident"] = float(programs["resident"])
            out["memory/temp_bytes_max"] = float(programs["temp"])
            if now.in_use is not None and self._state:
                out["memory/untracked_bytes"] = now.in_use - out["memory/state_bytes"] - out["memory/code_bytes"]
        return out

    def log_account(self) -> None:
        """One line when a cycle has closed, and again only when the need has
        grown by a hundredth."""
        a, programs = self._last, self._programs
        need = a.get("memory/need_bytes", 0.0)
        if programs is None or "memory/state_bytes" not in a or (self._logged and need <= 1.01 * self._logged):
            return
        self._logged = need or 1.0  # no reservation reported: the line is logged once
        gib = lambda key: a.get(key, 0.0) / 2**30  # noqa: E731
        peak = programs.get("temp_program_peak", 0)
        logger.info(
            "memory account: state %.3f GiB (params %.3f, optimizer %.3f, reference %.3f), code "
            "%.3f GiB in %d programs; after a step's fence %.3f GiB in use, %.3f untracked; "
            "largest temporaries by the compiler %.3f GiB (%s%s), reserved for running programs "
            "by the runtime %s; memory/need_bytes %s of %.3f",
            gib("memory/state_bytes"), gib("memory/params_bytes"), gib("memory/opt_state_bytes"),
            gib("memory/ref_bytes"), gib("memory/code_bytes"), a["memory/programs_resident"],
            gib("memory/device_bytes_in_use"), gib("memory/untracked_bytes"),
            gib("memory/temp_bytes_max"), programs["temp_program"],
            f"; its own peak by the compiler {peak / 2**30:.3f}" if peak else "",
            f"{gib('memory/device_reserved_bytes'):.3f}" if need else "not reported",
            f"{need / 2**30:.3f} GiB = peak {gib('memory/device_peak_bytes'):.3f} + reserved"
            if need else "absent",
            gib("memory/device_limit_bytes"),
        )

    def collect(self, programs: Optional[Dict[str, Any]] = None) -> Dict[str, float]:
        out: Dict[str, float] = {}
        now = self.read()
        in_use, limit = now.in_use, now.limit
        for field, value in now._asdict().items():
            if value is not None:
                out[_KEYS[field]] = value
        rss = self._host_rss_bytes()
        if rss is not None:
            out["memory/host_rss_bytes"] = rss
        out.update(self._account(now, programs))
        self._last, self._programs = out, programs
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
