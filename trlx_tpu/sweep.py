"""HPO sweep runner: dot-path hyperparameter spaces over a user script.

Capability parity with ``trlx/sweep.py:17-267`` (Ray Tune), rebuilt without a
Ray dependency: trials are subprocesses of the user script (same isolation
property Ray gave the reference — a fresh JAX runtime per trial, no compiled
-program or global-mesh leakage), the search space grammar is identical
(``strategy`` + ``values`` per dot-path key, ``tune_config`` block), and
results aggregate into a JSONL table + ranked report instead of a W&B
report (``trlx/sweep.py:177-264``).

Usage (same CLI shape as the reference)::

    python -m trlx_tpu.sweep --config examples/sweeps/ppo_sweep.yml \
        examples/randomwalks/ppo_randomwalks.py

The user script must expose ``main(hparams: dict)`` (every example does);
each trial invokes ``script.py '<json hparams>'`` with
``TRLX_TPU_SWEEP_RESULT`` pointing at the trial's result file, which the
trainer's learn loop writes at every evaluation (so early-stopped or crashed
trials still report their last metric).

Search algorithms: ``random`` (reference default), ``grid`` (via
``grid`` strategies), ``quasirandom`` (Halton — lower discrepancy coverage
than random at small trial counts; beyond the reference), and ``bayesopt``
(alias ``tpe``): an in-repo Tree-structured Parzen Estimator — the
reference's adaptive-search capability (``trlx/sweep.py:103-133``, Ray's
``BayesOptSearch``/``TuneBOHB``) without the external dependency. Every
strategy is a deterministic map from a unit coordinate ``u`` ∈ [0,1), so
all three samplers share one space: random draws u uniformly, quasirandom
from a Halton sequence, and TPE models completed trials' u-vectors with
good/bad Parzen mixtures and proposes the candidate maximizing their
density ratio. Schedulers: ``fifo`` (every trial runs its full budget) or
``asha``/``hyperband`` — successive halving over a budget dot-path (the
reference's Ray HyperBandScheduler capability, adapted to sequential
subprocess trials: promotions rerun at the larger budget).

Cluster dispatch (the reference's Ray trial placement,
``trlx/sweep.py:267-348``), all via ``tune_config``:

- ``launcher``: shell-line template used to start each trial process,
  e.g. ``"ssh -tt {host} env {env_remote} {python} {script}
  {hparams_remote}"`` — ``{env}``/``{env_remote}`` expand to the trial's
  ``TRLX_TPU_*`` contract (+ ``PYTHONPATH``) as ``k=v`` assignments (remote
  shells don't inherit the sweep's environment); the ``_remote`` variants
  carry an extra quoting layer that survives the remote shell's re-split,
  and ``-tt`` makes a terminated ssh client hang up the remote trial;
- ``hosts``: a free-slot pool — each trial borrows an entry for its
  whole run, so two in-flight trials never share one. Entries are a host
  or a comma-separated group (one process per pod host, coordinator on
  the first). Accelerator trials parallelize across hosts up to one
  in-flight trial per host (clamped);
- ``procs_per_trial``: spawn N coordinated processes per trial over the
  ``TRLX_TPU_COORDINATOR``/``NUM_PROCESSES``/``PROCESS_ID`` multi-host
  contract (one trial = one jax.distributed cluster; rank 0 writes the
  result file).

Reporting: trials stream a per-trial JSONL tracker under the sweep dir
(``tune_config.trial_curves: false`` keeps the script's own tracker), and
``report.md`` renders the ranked table plus each trial's metric curve
(sparklines; raw series in ``curves.json``) — the reference's W&B-report
capability offline. ``tune_config.wandb_report: true`` additionally
publishes the curves to a W&B run (opt-in: an unauthenticated wandb.init
blocks on a login prompt).

Results flow through ``TRLX_TPU_SWEEP_RESULT`` paths under the sweep's
output dir, so remote hosts must share that filesystem (NFS/GCS-fuse — the
standard pod setup; Ray ships results through its object store instead).
"""

import argparse
import functools
import importlib.util
import itertools
import json
import os
import re
import subprocess
import sys
import threading
import time
from concurrent.futures import ThreadPoolExecutor
from dataclasses import dataclass, field
from typing import Any, Dict, Iterator, List, Optional, Tuple

import numpy as np
import yaml

from trlx_tpu.utils import logging

logger = logging.get_logger(__name__)

_PRIMES = (2, 3, 5, 7, 11, 13, 17, 19, 23, 29, 31, 37, 41, 43, 47, 53)


def _norm_inv_cdf(u: float) -> float:
    """Standard-normal inverse CDF (stdlib; keeps randn strategies u-driven)."""
    from statistics import NormalDist

    return NormalDist().inv_cdf(min(max(u, 1e-9), 1 - 1e-9))


def _halton(index: int, base: int) -> float:
    """Van der Corput radical inverse of ``index`` in ``base`` ∈ (0, 1)."""
    result, f = 0.0, 1.0
    i = index
    while i > 0:
        f /= base
        result += f * (i % base)
        i //= base
    return result


@dataclass
class ParamDef:
    """One swept hyperparameter: a dot-path key + sampling strategy."""

    key: str
    strategy: str
    values: List[Any]

    def sample(self, u: float, rng: Optional[np.random.RandomState] = None) -> Any:
        """Map a unit coordinate ``u`` ∈ [0,1) to a value. Every strategy is
        a deterministic function of ``u`` so random, quasirandom, and TPE
        sampling all operate in one shared unit cube (``rng`` is accepted
        for backward compatibility and unused)."""
        del rng
        s, v = self.strategy, self.values
        if s == "uniform":
            return float(v[0] + u * (v[1] - v[0]))
        if s == "quniform":
            q = v[2]
            return float(np.round((v[0] + u * (v[1] - v[0])) / q) * q)
        if s == "loguniform":
            lo, hi = np.log(v[0]), np.log(v[1])
            return float(np.exp(lo + u * (hi - lo)))
        if s == "qloguniform":
            lo, hi, q = np.log(v[0]), np.log(v[1]), v[3]
            return float(np.round(np.exp(lo + u * (hi - lo)) / q) * q)
        if s == "randn":
            mean, sd = v
            return float(mean + sd * _norm_inv_cdf(u))
        if s == "qrandn":
            mean, sd, q = v
            return float(np.round((mean + sd * _norm_inv_cdf(u)) / q) * q)
        if s == "randint":
            return int(v[0] + int(u * (v[1] - v[0])))
        if s == "qrandint":
            q = v[2]
            return int(np.round((v[0] + u * (v[1] - v[0])) / q) * q)
        if s == "lograndint":
            lo, hi = np.log(v[0]), np.log(v[1])
            return int(np.exp(lo + u * (hi - lo)))
        if s == "qlograndint":
            lo, hi, q = np.log(v[0]), np.log(v[1]), v[3]
            return int(np.round(np.exp(lo + u * (hi - lo)) / q) * q)
        if s == "choice":
            return v[min(int(u * len(v)), len(v) - 1)]
        raise ValueError(f"Unknown strategy '{s}' for {self.key}")


@dataclass
class SweepSpace:
    """Parsed sweep config: sampled params + grid params + tune settings."""

    sampled: List[ParamDef] = field(default_factory=list)
    grid: List[ParamDef] = field(default_factory=list)
    tune: Dict[str, Any] = field(default_factory=dict)

    @classmethod
    def from_config(cls, config: Dict[str, Any]) -> "SweepSpace":
        space = cls()
        for key, value in config.items():
            if key in ("tune_config", "tune"):
                space.tune = dict(value)
                continue
            if not isinstance(value, dict) or "strategy" not in value:
                raise ValueError(
                    f"Sweep entry '{key}' must be a dict with 'strategy' and 'values'"
                )
            pd = ParamDef(key, value["strategy"], value.get("values", []))
            (space.grid if pd.strategy == "grid" else space.sampled).append(pd)
        return space

    def grid_points(self) -> List[Dict[str, Any]]:
        """Cartesian product of the grid-strategy params (``[{}]`` if none)."""
        if not self.grid:
            return [{}]
        grid_axes = [[(p.key, v) for v in p.values] for p in self.grid]
        return [dict(combo) for combo in itertools.product(*grid_axes)]

    def realize(self, point: Dict[str, Any], us: np.ndarray) -> Dict[str, Any]:
        """One grid point + a unit-cube coordinate vector → hparam dict."""
        hp = dict(point)
        for j, p in enumerate(self.sampled):
            hp[p.key] = p.sample(float(us[j]))
        return hp

    def trials(self, num_samples: int, seed: int = 0, search_alg: str = "random") -> Iterator[Dict[str, Any]]:
        """Yield hparam dicts: the cartesian grid × ``num_samples`` draws of
        the sampled params (non-adaptive algorithms only — ``bayesopt``
        needs trial feedback and runs through :func:`run_sweep`)."""
        searcher = Searcher(len(self.sampled), search_alg, seed)
        if searcher.adaptive:
            raise ValueError(
                f"search_alg '{search_alg}' is adaptive — it proposes trials "
                "from completed results and only runs through run_sweep()"
            )
        for _ in range(max(1, num_samples)):
            us = searcher.propose([])
            for point in self.grid_points():
                yield self.realize(point, us)
                if searcher.alg == "random":
                    # fresh coordinates per grid point: random explores
                    # |grid| x num_samples distinct sampled configs
                    # (quasirandom keeps one Halton row per draw)
                    us = searcher.propose([])


class Searcher:
    """Sequential trial proposer over the unit cube shared by every
    :class:`ParamDef` strategy.

    - ``random``: i.i.d. uniform (the reference's Ray Tune default).
    - ``quasirandom``: Halton sequence — stratified coverage at small trial
      counts (beyond the reference).
    - ``bayesopt`` / ``tpe``: Tree-structured Parzen Estimator, the adaptive
      capability the reference delegates to Ray's BayesOptSearch/TuneBOHB
      (``trlx/sweep.py:103-133``). After a quasirandom warmup, completed
      trials are split into good/bad by metric quantile (γ = 0.25); per
      dimension a Parzen mixture (Gaussians at observed coordinates + a
      uniform prior component) models each set, candidates are drawn from
      the good mixture, and the one maximizing ``log l(u|good) −
      log l(u|bad)`` is proposed — expected-improvement-proportional
      acquisition, per Bergstra et al. 2011.
    """

    def __init__(
        self,
        ndims: int,
        alg: str = "random",
        seed: int = 0,
        gamma: float = 0.25,
        n_candidates: int = 24,
        n_startup: Optional[int] = None,
    ):
        if alg not in ("random", "quasirandom", "bayesopt", "tpe"):
            raise ValueError(
                f"search_alg '{alg}' not supported "
                "(random, quasirandom, bayesopt/tpe)"
            )
        self.ndims = ndims
        self.alg = alg
        self.rng = np.random.RandomState(seed)
        self.gamma = gamma
        self.n_candidates = n_candidates
        self.n_startup = n_startup or max(4, 2 * ndims)
        self._draw = 0

    @property
    def adaptive(self) -> bool:
        return self.alg in ("bayesopt", "tpe")

    def propose(self, history: List[Tuple[List[float], float]]) -> np.ndarray:
        """Next unit-cube point. ``history`` holds completed trials as
        ``(u_vector, metric)`` with larger metric = better (callers negate
        for minimization); non-adaptive algorithms ignore it."""
        self._draw += 1
        halton_row = np.array(
            [_halton(self._draw, _PRIMES[j % len(_PRIMES)]) for j in range(self.ndims)]
        )
        if self.alg == "random":
            return self.rng.rand(self.ndims)
        if self.alg == "quasirandom" or len(history) < self.n_startup:
            return halton_row
        ordered = sorted(history, key=lambda t: -t[1])
        n_good = max(2, int(np.ceil(self.gamma * len(ordered))))
        good = np.asarray([u for u, _ in ordered[:n_good]], float)
        bad = np.asarray([u for u, _ in ordered[n_good:]], float)
        us = np.empty(self.ndims)
        for j in range(self.ndims):
            cands = self._parzen_draw(good[:, j])
            score = self._parzen_logpdf(cands, good[:, j]) - self._parzen_logpdf(
                cands, bad[:, j] if bad.size else np.empty(0)
            )
            us[j] = cands[int(np.argmax(score))]
        return us

    @staticmethod
    def _bandwidth(n: int) -> float:
        return float(np.clip(1.06 * 0.3 / max(n, 1) ** 0.2, 0.06, 0.5))

    def _parzen_draw(self, centers: np.ndarray) -> np.ndarray:
        """Candidates from the good mixture (uniform component included)."""
        bw = self._bandwidth(len(centers))
        picks = self.rng.randint(-1, len(centers), size=self.n_candidates)
        cands = np.where(
            picks < 0,
            self.rng.rand(self.n_candidates),
            centers[np.clip(picks, 0, None)] + bw * self.rng.randn(self.n_candidates),
        )
        return np.clip(cands, 0.0, 1.0 - 1e-9)

    def _parzen_logpdf(self, x: np.ndarray, centers: np.ndarray) -> np.ndarray:
        """log density of the Parzen mixture: Gaussians at ``centers`` plus
        one uniform prior component (keeps the ratio bounded off-support)."""
        if centers.size == 0:
            return np.zeros_like(x)
        bw = self._bandwidth(len(centers))
        z = (x[:, None] - centers[None, :]) / bw
        comps = np.exp(-0.5 * z**2) / (bw * np.sqrt(2 * np.pi))
        dens = (comps.sum(axis=1) + 1.0) / (len(centers) + 1)
        return np.log(dens + 1e-12)


_PORT_LOCK = threading.Lock()
_PORT_COUNTER = itertools.count(29500 + (os.getpid() % 997))


def _next_coordinator_port() -> int:
    """Sweep-unique coordinator port. A bind-then-release probe would race
    under concurrent trials (two trials drawing the same ephemeral port and
    cross-joining into one jax.distributed cluster) and proves nothing for a
    remote host anyway; a monotonic counter from a pid-offset base keeps
    every trial in this sweep on its own port. Collisions with unrelated
    services surface as an init failure of that one trial."""
    with _PORT_LOCK:
        return next(_PORT_COUNTER)


# the launcher template's placeholder names — substituted by literal token
# match (NOT str.format, whose index/attr/format-spec parsing corrupts shell
# constructs like ${arr[0]}, ${VAR:-default} or awk {print})
_PLACEHOLDERS = (
    "python", "script", "hparams", "hparams_remote", "host", "env", "env_remote"
)
_LAUNCHER_TOKENS = re.compile(r"\{(%s)\}" % "|".join(_PLACEHOLDERS))

# {token}-shaped survivors of substitution, for the typo check below; `$`
# lookbehind keeps shell ${VAR} expansions out, and the bare-word shape keeps
# awk '{print $1}' and friends out
_BRACE_TOKEN = re.compile(r"(?<!\$)\{([A-Za-z_][A-Za-z0-9_]*)\}")


@functools.lru_cache(maxsize=None)
def _warn_placeholder_near_misses(launcher: str) -> None:
    """A typo'd placeholder is not an error to the template engine — only the
    exact tokens substitute, so ``{pyhton}``, ``{hparam}``, or ``{HOST}``
    ride into the shell verbatim and the trial fails (or silently misruns)
    far from the typo. Scans the *template with the known tokens stripped*
    (never the substituted values — an hparam whose text contains
    ``{host}`` is the user's business) and warns for any surviving
    ``{token}`` that is case-insensitively equal or close (difflib ≥ 0.8) to
    a known placeholder; genuine shell/awk braces don't resemble one and
    stay silent. ``lru_cache``: the template is fixed for a sweep's
    lifetime, so the diagnosis prints once, not once per trial."""
    import difflib

    known = sorted(_PLACEHOLDERS)
    for token in _BRACE_TOKEN.findall(_LAUNCHER_TOKENS.sub("", launcher)):
        lowered = token.lower()
        if lowered in known:
            hint = lowered  # wrong case — {PYTHON} is not {python}
        else:
            close = difflib.get_close_matches(lowered, known, n=1, cutoff=0.8)
            if not close:
                continue
            hint = close[0]
        logger.warning(
            "launcher template: '{%s}' survived substitution but looks like "
            "the placeholder '{%s}' — it will reach the shell verbatim; "
            "known placeholders: %s",
            token, hint, ", ".join("{%s}" % k for k in known),
        )


def _trial_command(
    launcher: Optional[str],
    script: str,
    hparams: Dict[str, Any],
    host: Optional[str],
    env: Dict[str, str],
    extra_keys: Tuple[str, ...] = (),
):
    """Build one trial process's command: an argv list (no launcher) or a
    shell line (launcher template — run with ``shell=True`` so it behaves
    like the line the user wrote).

    Template placeholders: ``{python}``, ``{script}``, ``{host}``,
    ``{hparams}`` / ``{env}`` (shell-quoted once — for commands executed
    locally), and ``{hparams_remote}`` / ``{env_remote}`` (quoted twice —
    one layer is consumed by the local shell, the surviving layer protects
    the value when a remote shell re-splits the line, as ssh does). ``{env}``
    carries the trial's ``TRLX_TPU_*`` contract plus ``PYTHONPATH`` and
    ``JAX_PLATFORMS`` as ``k=v`` assignments: remote shells don't inherit
    the sweep's environment. Example::

        launcher: "ssh -tt {host} env {env_remote} {python} {script} {hparams_remote}"

    (``-tt`` so terminating the local ssh client also hangs up the remote
    trial — plain ssh would leave it running, holding the host's chip.)

    ONLY the exact tokens above are substituted (literal regex match, not
    ``str.format``); everything else — shell ``${HOME}``, ``${arr[0]}``,
    ``${VAR:-default}``, awk ``{print}``, lone braces — passes through
    verbatim with no escaping needed. ``{env}`` also carries every key the
    caller passed via ``extra_env`` (``extra_keys``) — a user-supplied
    ``WANDB_API_KEY`` or ``XLA_FLAGS`` must reach remote trials exactly
    like local no-launcher ones.

    Pass-through is also where typos hide: a ``{token}`` that *almost* names
    a placeholder (``{pyhton}``, ``{hparam}``, ``{HOST}``) survives
    substitution and reaches the shell verbatim, so the template is scanned
    and near-misses are warned about (genuine shell/awk braces and brace
    text inside substituted *values* stay silent — see
    :func:`_warn_placeholder_near_misses`).
    """
    if launcher is None:
        return [sys.executable, os.path.abspath(script), json.dumps(hparams)]
    import shlex

    def env_pairs(quote):
        return " ".join(
            f"{k}={quote(v)}"
            for k, v in sorted(env.items())
            if k.startswith("TRLX_TPU_")
            or k in ("JAX_PLATFORMS", "PYTHONPATH")
            or k in extra_keys
        )

    payload = json.dumps(hparams)
    values = {
        "python": shlex.quote(sys.executable),
        "script": shlex.quote(os.path.abspath(script)),
        "hparams": shlex.quote(payload),
        "hparams_remote": shlex.quote(shlex.quote(payload)),
        "host": host or "localhost",
        "env": env_pairs(shlex.quote),
        "env_remote": env_pairs(lambda v: shlex.quote(shlex.quote(v))),
    }
    _warn_placeholder_near_misses(launcher)
    return _LAUNCHER_TOKENS.sub(lambda m: values[m.group(1)], launcher)


def _wait_or_stop(procs: List[subprocess.Popen], timeout: Optional[float], log) -> int:
    """Wait on every trial process; on timeout SIGTERM (twice, 30 s grace
    each) so the trial can checkpoint and exit, then SIGKILL: a trial left
    running would keep the chip from every trial after it. Returns max rc
    (-1 on timeout)."""
    import signal

    deadline = None if timeout is None else time.time() + timeout
    rc = 0
    timed_out = False
    for proc in procs:
        left = None if deadline is None else max(0.1, deadline - time.time())
        try:
            rc = max(rc, abs(proc.wait(timeout=left)))
            continue
        except subprocess.TimeoutExpired:
            pass
        timed_out = True

        def _signal(sig, p=proc):
            # shell-launched trials run in their own session: signal that
            # whole group so it reaches the trial, not just /bin/sh. ONLY
            # when the child leads its own group — killpg on a child in the
            # sweep's group would signal the sweep itself.
            try:
                pgid = os.getpgid(p.pid)
                if pgid == p.pid:
                    os.killpg(pgid, sig)
                else:
                    p.send_signal(sig)
            except (ProcessLookupError, PermissionError, OSError):
                p.send_signal(sig)

        for sig in (signal.SIGTERM, signal.SIGTERM, signal.SIGKILL):
            _signal(sig)
            try:
                proc.wait(timeout=30)
                log.write(
                    f"\nsweep: trial stopped ({sig.name}) after {timeout}s timeout\n"
                )
                break
            except subprocess.TimeoutExpired:
                continue
    # a real failure code from any process outranks the generic timeout mark
    return rc if rc > 0 else (-1 if timed_out else rc)


def _trial_platform(env: Dict[str, str]) -> str:
    """The platform a trial's processes will ask JAX for:
    ``TRLX_TPU_PLATFORM`` (read by ``initialize_runtime``), else
    ``JAX_PLATFORMS``; empty = whatever JAX finds, i.e. the accelerator."""
    return env.get("TRLX_TPU_PLATFORM", env.get("JAX_PLATFORMS", ""))


def run_trial(
    script: str,
    hparams: Dict[str, Any],
    result_path: str,
    log_path: str,
    timeout: Optional[float] = None,
    extra_env: Optional[Dict[str, str]] = None,
    launcher: Optional[str] = None,
    host: Optional[str] = None,
    procs_per_trial: int = 1,
) -> int:
    """One trial: ``python script.py '<json>'`` with the result file
    advertised via ``TRLX_TPU_SWEEP_RESULT``.

    Multi-host dispatch (the reference's Ray-cluster trial placement,
    ``trlx/sweep.py:267-348``): ``launcher`` is a command template (see
    :func:`_trial_command`) used to place the processes — e.g. over ssh —
    and ``procs_per_trial > 1`` spawns that many coordinated processes per
    trial over the ``TRLX_TPU_COORDINATOR``/``NUM_PROCESSES``/``PROCESS_ID``
    contract (``trlx_tpu.trlx.initialize_runtime``). ``host`` may be a
    comma-separated group (``"hostA,hostB"``): process ``i`` lands on
    ``group[i % len(group)]`` — one process per pod host — and the
    coordinator is process 0's host. The trainer reports sweep results from
    rank 0 only, so the one ``result_path`` stays single-writer."""
    env = dict(os.environ)
    # trials run with cwd at the script; any relative path we hand them
    # would resolve against that cwd, not the sweep's
    env["TRLX_TPU_SWEEP_RESULT"] = os.path.abspath(result_path)
    # trials run with cwd at the script (for its local imports); make this
    # trlx_tpu installation importable there too
    pkg_root = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    env["PYTHONPATH"] = os.pathsep.join(
        p for p in (pkg_root, env.get("PYTHONPATH")) if p
    )
    if extra_env:
        env.update(extra_env)
    group = (host or "localhost").split(",")
    if procs_per_trial > len(group) and _trial_platform(env).lower() != "cpu":
        # a chip belongs to one process at a time: two of a trial's
        # processes on one host would each wait for the other's chip
        raise ValueError(
            f"procs_per_trial={procs_per_trial} accelerator processes need "
            f"a host each, got {len(group)} ({','.join(group)}): list one "
            "host per process in tune_config.hosts (\"hostA,hostB\"), or "
            "run CPU trials (JAX_PLATFORMS=cpu)"
        )
    coordinator = None
    if procs_per_trial > 1:
        coordinator = f"{group[0]}:{_next_coordinator_port()}"
    with open(log_path, "a") as log:
        procs = []
        for pid_i in range(max(1, procs_per_trial)):
            penv = dict(env)
            if coordinator is not None:
                penv.update(
                    TRLX_TPU_COORDINATOR=coordinator,
                    TRLX_TPU_NUM_PROCESSES=str(procs_per_trial),
                    TRLX_TPU_PROCESS_ID=str(pid_i),
                )
            cmd = _trial_command(
                launcher, script, hparams, group[pid_i % len(group)], penv,
                extra_keys=tuple(extra_env or ()),
            )
            procs.append(
                subprocess.Popen(
                    cmd,
                    shell=isinstance(cmd, str),
                    # own session, so timeout SIGTERMs reach the whole
                    # launcher process group (shell + ssh client)
                    start_new_session=isinstance(cmd, str),
                    cwd=os.path.dirname(os.path.abspath(script)) or None,
                    env=penv,
                    stdout=log,
                    stderr=subprocess.STDOUT,
                )
            )
        return _wait_or_stop(procs, timeout, log)


def run_sweep(
    script: str,
    config: Dict[str, Any],
    output_dir: str,
    num_samples: Optional[int] = None,
    seed: int = 0,
    trial_timeout: Optional[float] = None,
    extra_env: Optional[Dict[str, str]] = None,
    max_concurrent: int = 1,
) -> List[Dict[str, Any]]:
    """Run the sweep's trials (subprocesses of the user script), logging a
    JSONL results table, and return the records ranked best-first.

    Concurrency (``max_concurrent`` / ``tune_config.max_concurrent``): up to
    N trials run at once in a subprocess pool, the reference's Ray Tune
    parallel-trials capability (``trlx/sweep.py:267-347``, per-trial
    resources).  Parallel trials only make sense on a CPU mesh (one process
    per trial); when the trials would target a single accelerator the sweep
    serializes automatically with a warning — pass
    ``extra_env={"JAX_PLATFORMS": "cpu"}`` (CLI ``--cpu-trials``) to opt
    into parallel CPU trials.  Adaptive search (TPE) under concurrency
    proposes in chunks of ``max_concurrent`` from the history completed so
    far — the same stale-history compromise Ray makes.

    Schedulers (``tune_config.scheduler``): ``fifo`` (default — every trial
    runs its full budget, the reference's default) or ``asha``/``hyperband``
    — synchronous successive halving, the reference's Ray
    ``HyperBandScheduler`` capability (``trlx/sweep.py:136-174``): the
    initial population runs at a small budget (``grace_period`` steps of the
    ``budget_key`` dot-path, default ``train.total_steps``), the top
    ``1/reduction_factor`` fraction is promoted to an ``eta``-times larger
    budget, repeating until ``max_t``.  By default promoted trials RESUME
    from the rung's final interval checkpoint (each config gets a private
    ``train.checkpoint_dir`` under the sweep dir and promotions set
    ``train.resume_from_checkpoint``); set ``tune_config.asha_resume: false``
    to rerun promotions from scratch instead (e.g. when the user script
    overrides checkpointing itself).
    """
    space = SweepSpace.from_config(config)
    tune = space.tune
    metric = tune.get("metric", "reward/mean")
    mode = tune.get("mode", "max")
    n = num_samples or int(tune.get("num_samples", 4))
    search_alg = tune.get("search_alg", "random")
    scheduler = tune.get("scheduler", "fifo")
    if scheduler not in ("fifo", "asha", "hyperband"):
        raise ValueError(
            f"scheduler '{scheduler}' not supported (fifo, asha/hyperband)"
        )
    max_concurrent = max(1, int(tune.get("max_concurrent", max_concurrent)))
    # cluster dispatch (reference: Ray trial placement, trlx/sweep.py:267-348)
    launcher = tune.get("launcher")
    hosts: List[str] = list(tune.get("hosts") or [])
    procs_per_trial = max(1, int(tune.get("procs_per_trial", 1)))
    trial_curves = bool(tune.get("trial_curves", True))
    wandb_report = bool(tune.get("wandb_report", False))
    if hosts and launcher is None:
        raise ValueError(
            "tune_config.hosts needs tune_config.launcher (a command template "
            "like \"ssh -tt {host} env {env_remote} {python} {script} "
            "{hparams_remote}\") to place trials on those hosts"
        )
    merged_env = dict(os.environ)
    merged_env.update(extra_env or {})
    trial_platform = _trial_platform(merged_env)
    if hosts and max_concurrent > len(hosts) and trial_platform.lower() != "cpu":
        # accelerator trials take a host-pool slot for their whole run, so
        # excess in-flight trials would just block on the pool; clamp loudly
        # instead of silently queueing (CPU trials are exempt below: host
        # sharing is safe there, so they skip the pool entirely)
        logger.warning(
            f"max_concurrent={max_concurrent} > {len(hosts)} hosts with "
            "accelerator trials; clamping to one in-flight trial per host"
        )
        max_concurrent = len(hosts)
    if max_concurrent > 1 and trial_platform.lower() != "cpu" and not hosts:
        logger.warning(
            f"max_concurrent={max_concurrent} but trials target the "
            "accelerator (JAX_PLATFORMS is not 'cpu'); a single chip cannot "
            "host concurrent trials — serializing. Pass --cpu-trials (or "
            "extra_env JAX_PLATFORMS=cpu) for parallel CPU-mesh trials."
        )
        max_concurrent = 1

    # trials run with their cwd at the user script — every path that crosses
    # the subprocess boundary (result files, per-trial logging dirs) must be
    # absolute or it lands next to the script instead of the sweep output
    output_dir = os.path.abspath(output_dir)
    os.makedirs(output_dir, exist_ok=True)
    results_path = os.path.join(output_dir, "results.jsonl")
    records: List[Dict[str, Any]] = []
    # Host assignment. Accelerator trials: a free-slot pool — a trial
    # borrows a host for its whole run, so two in-flight trials can never
    # share one chip (index-based cycling breaks the moment pool workers
    # finish out of order, e.g. big ASHA batches). CPU trials: host sharing
    # is safe, so skip the pool — a blocking pool would silently serialize
    # the supported oversubscribed-CPU sweep — and cycle hosts non-blocking.
    host_pool: Optional[Any] = None
    host_cycle: Optional[Any] = None
    if hosts:
        if trial_platform.lower() != "cpu":
            import queue

            host_pool = queue.Queue()
            for h in hosts:
                host_pool.put(h)
        else:
            host_cycle = iter(itertools.cycle(hosts))
    searcher = Searcher(len(space.sampled), search_alg, seed=seed)
    grid_points = space.grid_points()
    draws = max(1, n)
    sign = 1.0 if mode == "max" else -1.0
    lock = threading.Lock()
    logger.info(
        f"Sweep[{search_alg}/{scheduler}"
        + (f"/x{max_concurrent}" if max_concurrent > 1 else "")
        + f"]: {draws * len(grid_points)} base trials "
        f"of {os.path.basename(script)} → {output_dir}"
    )

    with open(results_path, "w") as results_f:

        def launch(hparams: Dict[str, Any], us: np.ndarray, rung: Optional[int] = None) -> Dict[str, Any]:
            with lock:  # reserve a trial index
                i = len(records)
                record: Dict[str, Any] = {"trial": i, "metric": None}
                records.append(record)
            t0 = time.time()
            result_path = os.path.join(output_dir, f"trial_{i:03d}.json")
            log_path = os.path.join(output_dir, f"trial_{i:03d}.log")
            # per-trial metric curves (the reference streams every trial to
            # W&B and renders a report of the curves, trlx/sweep.py:177-264;
            # here each trial gets a JSONL tracker under the sweep dir and
            # report() renders the curves). This overrides the script's own
            # tracker for the trial — the reference's Ray sweep routes trial
            # logging the same way; set tune_config.trial_curves: false to
            # keep the script's tracker instead. The injected plumbing keys
            # stay OUT of the recorded hparams (the record must reproduce
            # the winning config, not this sweep's local paths).
            user_hparams = hparams
            trial_dir = os.path.join(output_dir, f"trial_{i:03d}")
            stats_file = os.path.join(trial_dir, "stats.jsonl")
            if os.path.exists(stats_file):
                # JSONL trackers append, and report() reads this path
                # unconditionally: a rerun into the same output_dir must
                # never fuse (or inherit) a previous run's curves — cleared
                # even when this run injects no tracker
                os.remove(stats_file)
            if trial_curves and "train.tracker" not in hparams:
                hparams = dict(
                    hparams,
                    **{"train.logging_dir": trial_dir, "train.tracker": "jsonl"},
                )
            if host_pool is not None:
                trial_host = host_pool.get()
            elif host_cycle is not None:
                with lock:
                    trial_host = next(host_cycle)
            else:
                trial_host = None
            try:
                rc = run_trial(
                    script,
                    hparams,
                    result_path,
                    log_path,
                    trial_timeout,
                    extra_env,
                    launcher=launcher,
                    host=trial_host,
                    procs_per_trial=procs_per_trial,
                )
            finally:
                if host_pool is not None:
                    host_pool.put(trial_host)
            stats: Dict[str, Any] = {}
            if os.path.exists(result_path):
                with open(result_path) as f:
                    stats = json.load(f)
            record.update(
                hparams=user_hparams,
                u=[float(x) for x in us],
                rc=rc,
                runtime_s=round(time.time() - t0, 1),
                metric=stats.get("stats", {}).get(metric),
                stats=stats.get("stats", {}),
                iter_count=stats.get("iter_count"),
            )
            if rung is not None:
                record["rung"] = rung
            with lock:
                results_f.write(json.dumps(record) + "\n")
                results_f.flush()
            logger.info(
                f"trial {i}{'' if rung is None else f' (rung {rung})'}: rc={rc} "
                f"{metric}={record['metric']} ({record['runtime_s']}s) {hparams}"
            )
            return record

        def launch_batch(
            batch: List[Tuple[Dict[str, Any], np.ndarray, Optional[int]]]
        ) -> List[Dict[str, Any]]:
            """Run a batch of trials, up to ``max_concurrent`` at a time."""
            if max_concurrent <= 1 or len(batch) <= 1:
                return [launch(h, u, r) for h, u, r in batch]
            with ThreadPoolExecutor(max_workers=max_concurrent) as pool:
                futs = [pool.submit(launch, h, u, r) for h, u, r in batch]
                return [f.result() for f in futs]

        def next_us() -> np.ndarray:
            # TPE history: one entry per unit-cube point. ASHA promotions
            # re-launch the same u-vector at a larger budget — keep only the
            # highest-budget (latest-rung) metric per point so promoted
            # configs aren't double-weighted in the Parzen good set, while
            # the search still sees the most-converged estimate.
            by_u: Dict[Tuple[float, ...], Tuple[int, float]] = {}
            with lock:
                snapshot = list(records)
            for r in snapshot:
                if r.get("u") is None or r.get("metric") is None:
                    continue
                key = tuple(r["u"])
                rung = r.get("rung") or 0
                if key not in by_u or rung >= by_u[key][0]:
                    by_u[key] = (rung, sign * r["metric"])
            history = [(list(k), m) for k, (_, m) in by_u.items()]
            return searcher.propose(history)

        def proposals() -> Iterator[Tuple[Dict[str, Any], np.ndarray]]:
            """Lazy (hparams, u) stream: proposed only when consumed, so
            adaptive search sees every completed trial so far. random draws
            fresh coordinates per grid point (full |grid| x num_samples
            coverage); quasirandom keeps one Halton row per draw; TPE
            proposes once per draw — grid dims are marginalized out."""
            for _ in range(draws):
                us = None
                for point in grid_points:
                    if us is None or searcher.alg == "random":
                        us = next_us()
                    yield space.realize(point, us), us

        if scheduler == "fifo":
            # chunks of max_concurrent keep adaptive search fed with
            # completed results between batches
            batch: List[Tuple[Dict[str, Any], np.ndarray, Optional[int]]] = []
            for hparams, us in proposals():
                batch.append((hparams, us, None))
                if len(batch) >= max_concurrent:
                    launch_batch(batch)
                    batch = []
            if batch:
                launch_batch(batch)
        else:
            _run_asha(tune, proposals(), launch_batch, sign, output_dir, max_concurrent)

    def rank_key(r):
        m = r["metric"]
        if m is None:
            return float("inf")
        return -m if mode == "max" else m

    records.sort(key=rank_key)
    report(records, metric, mode, output_dir, wandb_report=wandb_report)
    return records


def _run_asha(
    tune: Dict[str, Any],
    proposals: Iterator[Tuple[Dict[str, Any], np.ndarray]],
    launch_batch,
    sign: float,
    output_dir: str,
    max_concurrent: int = 1,
) -> None:
    """Synchronous successive halving over the trial budget.

    Rung r runs its population with the ``budget_key`` dot-path overridden to
    ``grace_period * reduction_factor**r`` (capped at ``max_t``); the top
    ``1/reduction_factor`` fraction by metric is promoted to the next rung.
    The capability analogue of Ray's HyperBandScheduler in the reference
    (``trlx/sweep.py:136-174``) adapted to subprocess trials.

    By default each config gets a private checkpoint dir
    (``<output_dir>/ckpt_cfg<i>`` via ``train.checkpoint_dir``) and promoted
    trials set ``train.resume_from_checkpoint`` so rung r+1 CONTINUES from
    rung r's final interval checkpoint instead of reburning its compute —
    Ray's pause/resume actor semantics. ``tune_config.asha_resume: false``
    (or custom ``checkpoint_dir_key``/``resume_key``) opts out/retargets.
    """
    eta = int(tune.get("reduction_factor", 3))
    if eta < 2:
        raise ValueError(f"reduction_factor must be >= 2, got {eta}")
    max_t = tune.get("max_t")
    if max_t is None:
        raise ValueError("asha scheduler requires tune_config.max_t (final budget)")
    max_t = int(max_t)
    grace = int(tune.get("grace_period", max(1, max_t // eta**2)))
    budget_key = tune.get("budget_key", "train.total_steps")
    resume = bool(tune.get("asha_resume", True))
    ckpt_key = tune.get("checkpoint_dir_key", "train.checkpoint_dir")
    resume_key = tune.get("resume_key", "train.resume_from_checkpoint")

    def with_ckpt(hparams: Dict[str, Any], cid: int, promoted: bool) -> Dict[str, Any]:
        if not resume:
            return hparams
        hp = dict(hparams)
        hp[ckpt_key] = os.path.join(output_dir, f"ckpt_cfg{cid:03d}")
        if promoted:
            hp[resume_key] = True
        return hp

    t = min(grace, max_t)
    # rung 0 consumes the proposal stream lazily in batches, so adaptive
    # search (bayesopt) sees completed low-budget trials between batches —
    # draining it upfront would silently degrade TPE to its warmup
    results = []
    cid = 0
    pending: List[Tuple[int, Dict[str, Any], np.ndarray]] = []

    def flush_rung0():
        nonlocal results
        if not pending:
            return
        recs = launch_batch(
            [({**with_ckpt(h, c, False), budget_key: t}, us, 0) for c, h, us in pending]
        )
        for (c, h, us), rec in zip(pending, recs):
            if rec["metric"] is not None:
                results.append((sign * rec["metric"], c, h, us))
        pending.clear()

    for hparams, us in proposals:
        pending.append((cid, hparams, us))
        cid += 1
        if len(pending) >= max_concurrent:
            flush_rung0()
    flush_rung0()

    rung = 0
    while t < max_t and results:
        results.sort(key=lambda r: -r[0])
        n_keep = max(1, int(np.ceil(len(results) / eta)))
        survivors = results[:n_keep]
        # a lone survivor jumps straight to the final budget: the winning
        # config always gets its full max_t run
        t = max_t if len(survivors) <= 1 else min(t * eta, max_t)
        rung += 1
        recs = launch_batch(
            [
                ({**with_ckpt(h, c, True), budget_key: t}, us, rung)
                for _, c, h, us in survivors
            ]
        )
        results = [
            (sign * rec["metric"], c, h, us)
            for (_, c, h, us), rec in zip(survivors, recs)
            if rec["metric"] is not None
        ]


_SPARK = "▁▂▃▄▅▆▇█"


def _sparkline(series: List[float]) -> str:
    finite = [v for v in series if np.isfinite(v)]
    if not finite:
        return ""
    lo, hi = min(finite), max(finite)
    span = (hi - lo) or 1.0
    return "".join(
        _SPARK[int((v - lo) / span * (len(_SPARK) - 1))] if np.isfinite(v) else " "
        for v in series
    )


def _trial_curve(output_dir: str, trial: int, metric: str) -> List[float]:
    """The trial's metric series from its JSONL tracker stream."""
    path = os.path.join(output_dir, f"trial_{trial:03d}", "stats.jsonl")
    if not os.path.exists(path):
        return []
    series = []
    with open(path) as f:
        for line in f:
            try:
                row = json.loads(line)
                if metric in row:
                    series.append(float(row[metric]))
            except (ValueError, TypeError):
                continue  # a malformed line must not cost the whole report
    return series


def report(
    records: List[Dict[str, Any]],
    metric: str,
    mode: str,
    output_dir: str,
    wandb_report: bool = False,
) -> None:
    """Sweep report: ranked table + per-trial metric curves — the capability
    of the reference's W&B report (``trlx/sweep.py:177-264``, line plots of
    every trial's metric over steps), rendered offline as sparkline rows in
    ``report.md`` with the raw series in ``curves.json``. With
    ``wandb_report=True`` (``tune_config.wandb_report`` — opt-in: an
    unauthenticated ``wandb.init`` blocks on a login prompt, so it must
    never run by surprise) the same curves also publish to a W&B run
    (:func:`publish_wandb_report`)."""
    lines = [f"# Sweep report — {metric} ({mode})", ""]
    lines.append("| rank | trial | " + metric + " | rc | hparams |")
    lines.append("|---|---|---|---|---|")
    for rank, r in enumerate(records):
        lines.append(
            f"| {rank} | {r['trial']} | {r['metric']} | {r['rc']} | `{json.dumps(r['hparams'])}` |"
        )
    best = records[0] if records else None
    if best is not None and best["metric"] is not None:
        lines += ["", f"Best: trial {best['trial']} → {metric}={best['metric']}", f"```json\n{json.dumps(best['hparams'], indent=2)}\n```"]

    curves = {r["trial"]: _trial_curve(output_dir, r["trial"], metric) for r in records}
    if any(curves.values()):
        lines += ["", f"## {metric} over evaluations", ""]
        lines.append("| trial | curve | first | last | n |")
        lines.append("|---|---|---|---|---|")
        for r in records:
            series = curves[r["trial"]]
            if not series:
                continue
            lines.append(
                f"| {r['trial']} | `{_sparkline(series)}` | {series[0]:.4g} "
                f"| {series[-1]:.4g} | {len(series)} |"
            )
        with open(os.path.join(output_dir, "curves.json"), "w") as f:
            json.dump({str(k): v for k, v in curves.items()}, f, indent=2)
    else:
        # a curve-less run must not leave a previous run's curves.json
        # sitting next to a fresh report.md
        stale = os.path.join(output_dir, "curves.json")
        if os.path.exists(stale):
            os.remove(stale)

    text = "\n".join(lines)
    with open(os.path.join(output_dir, "report.md"), "w") as f:
        f.write(text + "\n")
    if logging.get_verbosity() <= logging.INFO:
        print(text)
    if wandb_report:
        publish_wandb_report(records, curves, metric, output_dir)


def publish_wandb_report(
    records: List[Dict[str, Any]],
    curves: Dict[int, List[float]],
    metric: str,
    output_dir: str,
) -> bool:
    """Publish the sweep summary + trial curves as a W&B run (reference
    capability: ``trlx/sweep.py:177-264`` builds a wandb Report of all trial
    charts). Graceful no-op (returns False) when wandb is missing, disabled,
    or offline — the markdown/JSON artifacts above are the offline record."""
    if os.environ.get("WANDB_MODE", "").lower() in ("disabled", "dryrun"):
        return False
    try:
        import wandb
    except ImportError:
        return False
    try:
        run = wandb.init(
            project=os.environ.get("WANDB_PROJECT", "trlx_tpu-sweeps"),
            name=os.path.basename(os.path.abspath(output_dir)),
            job_type="sweep-report",
        )
        table = wandb.Table(columns=["rank", "trial", metric, "hparams"])
        for rank, r in enumerate(records):
            table.add_data(rank, r["trial"], r["metric"], json.dumps(r["hparams"]))
        payload: Dict[str, Any] = {"ranking": table}
        series = [curves[r["trial"]] for r in records if curves.get(r["trial"])]
        if series:
            keys = [f"trial {r['trial']}" for r in records if curves.get(r["trial"])]
            xs = list(range(max(len(s) for s in series)))
            payload["curves"] = wandb.plot.line_series(
                xs=xs, ys=series, keys=keys, title=metric, xname="evaluation"
            )
        run.log(payload)
        run.finish()
        return True
    except Exception as e:  # network/auth problems must never fail the sweep
        logger.warning(f"W&B sweep report skipped: {e}")
        return False


def main(argv: Optional[List[str]] = None) -> int:
    parser = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    parser.add_argument("script", help="user script exposing main(hparams)")
    parser.add_argument("--config", required=True, help="sweep YAML (dot-path params + tune_config)")
    parser.add_argument("--output-dir", default=None)
    parser.add_argument("--num-samples", type=int, default=None)
    parser.add_argument("--seed", type=int, default=0)
    parser.add_argument(
        "--max-concurrent",
        type=int,
        default=1,
        help="run up to N trials at once (requires CPU-mesh trials; see --cpu-trials)",
    )
    parser.add_argument(
        "--cpu-trials",
        action="store_true",
        help="force each trial onto a CPU mesh (JAX_PLATFORMS=cpu) so trials "
        "can run concurrently without contending for the accelerator",
    )
    args = parser.parse_args(argv)

    with open(args.config) as f:
        config = yaml.safe_load(f)
    output_dir = args.output_dir or os.path.join(
        "sweeps", os.path.splitext(os.path.basename(args.script))[0] + time.strftime("-%y%m%d-%H%M%S")
    )
    extra_env = {"JAX_PLATFORMS": "cpu"} if args.cpu_trials else None
    records = run_sweep(
        args.script,
        config,
        output_dir,
        num_samples=args.num_samples,
        seed=args.seed,
        extra_env=extra_env,
        max_concurrent=args.max_concurrent,
    )
    return 0 if records and any(r["metric"] is not None for r in records) else 1


if __name__ == "__main__":
    sys.exit(main())
