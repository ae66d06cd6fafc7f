"""Test session config: force an 8-device virtual CPU mesh.

The reference has no multi-device tests at all (SURVEY.md §4); under JAX we can
exercise real sharding/collective paths on a host-platform mesh without TPUs.
Must run before jax initializes its backends, hence env vars at import time.
"""

import os

os.environ.setdefault("XLA_FLAGS", "--xla_force_host_platform_device_count=8")
os.environ["JAX_PLATFORMS"] = "cpu"
os.environ.setdefault("TRLX_TPU_NO_TQDM", "1")
# zero-egress container: skip HF hub lookups (and their long retry delays)
os.environ.setdefault("HF_HUB_OFFLINE", "1")
# Persistent compile cache: repeated test runs skip XLA compilation.
os.environ.setdefault("JAX_COMPILATION_CACHE_DIR", "/tmp/jax_test_cache")
os.environ.setdefault("JAX_PERSISTENT_CACHE_MIN_COMPILE_TIME_SECS", "0.5")

import pytest  # noqa: E402


@pytest.fixture()
def trlx_log_records():
    """Captured LogRecords from the ``trlx_tpu`` logger tree.

    The repo's logging setup (``trlx_tpu/utils/logging.py``) attaches its own
    handler and sets ``propagate=False`` on the package root, so pytest's
    ``caplog`` never sees these records — this fixture taps the package root
    directly."""
    import logging as _logging

    records = []

    class _Capture(_logging.Handler):
        def emit(self, record):
            records.append(record)

    handler = _Capture(level=_logging.DEBUG)
    logger = _logging.getLogger("trlx_tpu")
    logger.addHandler(handler)
    try:
        yield records
    finally:
        logger.removeHandler(handler)


@pytest.fixture(scope="session", autouse=True)
def _session_program_store(tmp_path_factory):
    """What a module- or session-scoped fixture builds is built before any
    test's own store exists: it goes to a store of this session (of this
    worker), never to ``programs/`` under the shared compile cache."""
    from trlx_tpu.utils import programs

    root = str(tmp_path_factory.mktemp("session_programs"))
    keep, programs.store_dir = programs.store_dir, lambda: root
    yield
    programs.store_dir = keep


@pytest.fixture(autouse=True)
def _own_program_store(tmp_path, monkeypatch):
    """Every test keeps its compiled programs (``trlx_tpu/utils/programs.py``)
    in a store of its own. JAX's compile cache above is ONE directory for every
    session, worker and tree, and tests patch functions in place that no key
    can see: a store under it would hand one test the program another
    compiled. The compile cache itself stays shared, so the suite's time holds."""
    from trlx_tpu.utils import programs

    monkeypatch.setattr(programs, "store_dir", lambda: str(tmp_path / "programs"))


# ---------------------------------------------------------------------------
# leaked-thread / leaked-process sentinel
# ---------------------------------------------------------------------------

# Threads allowed to outlast a test:
# - trlx-tpu-flops: the prewarmed MFU flops analysis is a one-shot daemon
#   deliberately left to finish in the background (trainer/base.py);
# - the persistent Orbax AsyncCheckpointer singleton's worker/executor
#   threads (utils/checkpoint.py keeps ONE checkpointer alive across saves
#   by design — its pool threads live with the process).
_SENTINEL_ALLOWED_THREADS = {"trlx-tpu-flops"}
_SENTINEL_ALLOWED_PREFIXES = (
    "ThreadPoolExecutor",
    # orbax AsyncCheckpointer internals (the persistent singleton's pools)
    "orbax",
    "async_save",
    "metadata_store",
    "base_pytree_ch",
    "array_ch",
)


@pytest.fixture(autouse=True)
def _leak_sentinel(request):
    """Fail any test that leaks a thread or child process — the dynamic
    complement of graftlint's GL403 thread-escape pass: an actor/worker
    thread the shutdown path forgot to join is invisible to a green
    assertion but races every test that follows it.

    Checked: non-daemon threads (nothing in this repo should ever create
    one outside the allowlisted pools), daemon threads named ``trlx-*``
    (every repo-spawned worker is name-tagged: pipeline workers, prefetch,
    async actors — all have owning close()/join() paths), and
    ``multiprocessing`` children. A short join grace absorbs shutdown
    paths that signal first and exit within milliseconds."""
    import threading

    before = {t.ident for t in threading.enumerate()}
    yield
    import multiprocessing
    import time as _time

    def _leaked():
        threads = []
        for t in threading.enumerate():
            if not t.is_alive() or t.ident in before:
                continue
            name = t.name or ""
            if name in _SENTINEL_ALLOWED_THREADS:
                continue
            if any(name.startswith(p) for p in _SENTINEL_ALLOWED_PREFIXES):
                continue
            if t.daemon and name.endswith("-guard"):
                # HostCallGuard's timed-out worker: deliberately abandoned
                # (Python can't kill a thread stuck in a dead endpoint);
                # daemon by design so it dies with the process
                continue
            if t.daemon and not name.startswith("trlx-"):
                continue  # runtime-internal daemons (jax, grpc, tqdm...)
            threads.append(t)
        procs = [p for p in multiprocessing.active_children() if p.is_alive()]
        return threads, procs

    threads, procs = _leaked()
    deadline = _time.monotonic() + 2.0
    while (threads or procs) and _time.monotonic() < deadline:
        for t in threads:
            t.join(timeout=0.2)
        for p in procs:
            p.join(timeout=0.2)
        threads, procs = _leaked()
    if threads or procs:
        names = [f"thread {t.name!r} (daemon={t.daemon})" for t in threads]
        names += [f"process pid={p.pid}" for p in procs]
        pytest.fail(
            f"leaked concurrency outlasts the test: {', '.join(names)} — "
            "join/close it in the owning shutdown path "
            "(docs/STATIC_ANALYSIS.md 'Thread escape')"
        )


def pytest_collection_modifyitems(config, items):
    """Fast tier: tests measured >= 8s (tests/slow_tests.txt) are auto-marked
    ``slow``, so ``pytest -m "not slow"`` is a <5-min inner loop while plain
    ``pytest tests/`` stays the full suite. Explicit ``@pytest.mark.slow``
    markers are unaffected."""
    import pytest

    list_path = os.path.join(os.path.dirname(__file__), "slow_tests.txt")
    if not os.path.exists(list_path):
        return
    with open(list_path) as f:
        slow = {l.strip() for l in f if l.strip() and not l.startswith("#")}
    for item in items:
        nodeid = item.nodeid
        base = nodeid.split("[", 1)[0]
        if nodeid in slow or base in slow:
            item.add_marker(pytest.mark.slow)


@pytest.fixture
def clean_trace_state():
    """What the text of a jaxpr depends on beside the code that is traced, for
    the tests that pin one (``programs_before_*.json``,
    ``smallthinker_ring_programs_before_exaone.json``): the default matmul
    precision, which five test modules set to ``highest`` at import, and the
    process-global mesh (``parallel/mesh.py::set_global_mesh``), which every
    trainer and some tests set and none resets: under a mesh left behind by an
    earlier test of the worker, a decode step's jaxpr carries the
    ``sharding_constraint`` of ``_activation_sharded`` (what failed
    ``test_ring_programs_are_the_ones_recorded_before_the_per_row_ring[decode]``
    in the whole run of PR 53 and passed alone: after ``tests/test_quantized_opt.py``
    or ``tests/test_setup_programs.py`` in one process it fails every time)."""
    import jax

    from trlx_tpu.parallel.mesh import get_global_mesh, set_global_mesh

    mesh = get_global_mesh()
    set_global_mesh(None)
    with jax.default_matmul_precision(None):
        yield
    set_global_mesh(mesh)

