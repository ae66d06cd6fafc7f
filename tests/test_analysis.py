"""graftlint (trlx_tpu/analysis): per-pass fixtures, baseline semantics,
and the tier-1 self-run over the real tree (docs/STATIC_ANALYSIS.md).

The self-run is the CI gate: any non-baselined finding on ``trlx_tpu/``,
or any stale baseline entry, fails ``pytest tests/``."""

import os
import subprocess
import sys
import textwrap

import pytest

from trlx_tpu.analysis import (
    AnalysisContext,
    Baseline,
    BaselineError,
    all_passes,
    main,
    run_analysis,
)

REPO_ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
TREE = os.path.join(REPO_ROOT, "trlx_tpu")
SCRIPTS = os.path.join(REPO_ROOT, "scripts")
BASELINE = os.path.join(REPO_ROOT, "GRAFTLINT_BASELINE.txt")


def lint_pkg(tmp_path, files, passes=None, name="pkg"):
    """Write a throwaway package and run passes over it."""
    root = tmp_path / name
    root.mkdir(exist_ok=True)
    (root / "__init__.py").write_text("")
    for relname, text in files.items():
        path = root / relname
        path.parent.mkdir(parents=True, exist_ok=True)
        path.write_text(textwrap.dedent(text))
    findings, _ctx = run_analysis(str(root), passes=passes)
    return findings


def codes(findings):
    return sorted(f.code for f in findings)


# ---------------------------------------------------------------------------
# host-sync (GL1xx)
# ---------------------------------------------------------------------------


def test_host_sync_positive(tmp_path):
    findings = lint_pkg(
        tmp_path,
        {
            "bad.py": """
            import jax
            import jax.numpy as jnp
            import numpy as np

            def traced(x, tracker):
                v = float(jnp.sum(x))
                print("debug")
                y = x.item()
                z = np.asarray(x)
                w = jax.device_get(x)
                tracker.log({"a/b": 1.0}, step=0)
                return v + y

            jax.jit(traced)
            """
        },
        passes=["host-sync"],
    )
    assert codes(findings) == [
        "GL101", "GL102", "GL103", "GL104", "GL105", "GL106",
    ]
    assert all("traced via root `traced`" in f.message for f in findings)


def test_host_sync_negative(tmp_path):
    # the same constructs OUTSIDE jit-reachable code are host-side and fine;
    # inside traced code, shape math and jnp conversions are fine too
    findings = lint_pkg(
        tmp_path,
        {
            "good.py": """
            import jax
            import jax.numpy as jnp
            import numpy as np

            def host_only(x):
                print("host")
                return float(np.asarray(x).sum())

            def traced(x):
                B = int(x.shape[0])          # shape math: static, no sync
                y = jnp.asarray(x) + B       # jnp conversion stays on device
                n = float("inf")             # literal, not an array
                return y * n

            jax.jit(traced)
            """
        },
        passes=["host-sync"],
    )
    assert findings == []


def test_host_sync_reaches_through_calls_and_references(tmp_path):
    # helper called from a jitted root — and a body passed by reference to
    # lax.while_loop — are both traced
    findings = lint_pkg(
        tmp_path,
        {
            "deep.py": """
            import jax

            def helper(x):
                return x.item()

            def root(x):
                def body(c):
                    return helper(c)
                def cond(c):
                    return c.any()
                return jax.lax.while_loop(cond, body, x)

            jax.jit(root)
            """
        },
        passes=["host-sync"],
    )
    assert codes(findings) == ["GL101"]
    assert findings[0].symbol == "helper"


# ---------------------------------------------------------------------------
# recompile-hazard (GL2xx)
# ---------------------------------------------------------------------------


def test_recompile_positive(tmp_path):
    findings = lint_pkg(
        tmp_path,
        {
            "bad.py": """
            import jax

            def loopy(fs):
                for f in fs:
                    g = jax.jit(f)
                h = jax.jit(lambda x: x + 1)
                return h

            def ranged(n, x):
                acc = x
                for _ in range(n):
                    acc = acc + 1
                return acc

            jax.jit(ranged)

            def closure_hazard(x):
                B, T = x.shape
                def inner(y):
                    return y.reshape(B, T)
                return jax.jit(inner)
            """
        },
        passes=["recompile-hazard"],
    )
    assert codes(findings) == ["GL201", "GL202", "GL203", "GL204"]
    gl201 = next(f for f in findings if f.code == "GL201")
    assert gl201.detail == "B,T"


def test_recompile_negative(tmp_path):
    # module-level jit, static_argnums, and non-shape closures are all fine
    findings = lint_pkg(
        tmp_path,
        {
            "good.py": """
            import functools
            import jax

            def ranged(n, x):
                acc = x
                for _ in range(n):
                    acc = acc + 1
                return acc

            jax.jit(ranged, static_argnums=(0,))

            @functools.partial(jax.jit, static_argnums=0)
            def decorated(n, x):
                for _ in range(n):
                    x = x + 1
                return x

            def build(scale):
                def inner(y):
                    return y * scale     # config constant, not shape-derived
                return jax.jit(inner)
            """
        },
        passes=["recompile-hazard"],
    )
    assert findings == []


# ---------------------------------------------------------------------------
# donation-safety (GL301)
# ---------------------------------------------------------------------------


def test_donation_read_after_donate(tmp_path):
    findings = lint_pkg(
        tmp_path,
        {
            "bad.py": """
            import jax

            def step_impl(s, b):
                return s

            step = jax.jit(step_impl, donate_argnums=(0,))

            def train(state, batch):
                new = step(state, batch)
                stale = state.params      # read after donation
                return new, stale
            """
        },
        passes=["donation-safety"],
    )
    assert codes(findings) == ["GL301"]
    assert findings[0].detail == "state"


def test_donation_rebind_is_clean(tmp_path):
    # `state = step(state, b)` rebinding — and reads before the donating
    # call — are the intended pattern
    findings = lint_pkg(
        tmp_path,
        {
            "good.py": """
            import jax

            def step_impl(s, b):
                return s, {}

            def train(state, batches):
                step = jax.jit(step_impl, donate_argnums=(0,))
                total = state.step
                for b in batches:
                    state, stats = step(state, b)
                return state
            """
        },
        passes=["donation-safety"],
    )
    assert findings == []


def test_donation_found_despite_nested_def_in_statement(tmp_path):
    # a nested def inside the same compound statement must not abort the
    # donation scan (the walk skips the def's subtree, not the statement)
    findings = lint_pkg(
        tmp_path,
        {
            "m.py": """
            import jax

            def step_impl(s, b):
                return s

            step = jax.jit(step_impl, donate_argnums=(0,))

            def check(x):
                return True

            def bad(state, b):
                if check(step(state, b)):
                    def helper():
                        return 1
                return state.params
            """
        },
        passes=["donation-safety"],
    )
    assert codes(findings) == ["GL301"]


def test_donation_through_factory_and_attr(tmp_path):
    # the trainer pattern: a factory method returns the donating callable,
    # an attribute holds it, another method calls it
    findings = lint_pkg(
        tmp_path,
        {
            "cls.py": """
            import jax

            class Trainer:
                def _build(self):
                    def step_fn(s, b):
                        return s, {}
                    return jax.jit(step_fn, donate_argnums=(0,))

                def setup(self):
                    self._step = self._build()

                def bad_step(self, batch):
                    new, stats = self._step(self.state, batch)
                    leak = self.state.params    # donated buffer read
                    return new, leak

                def good_step(self, batch):
                    self.state, stats = self._step(self.state, batch)
                    return self.state
            """
        },
        passes=["donation-safety"],
    )
    assert codes(findings) == ["GL301"]
    assert findings[0].symbol == "Trainer.bad_step"
    assert findings[0].detail == "self.state"


# ---------------------------------------------------------------------------
# lock-discipline (GL4xx)
# ---------------------------------------------------------------------------

_LOCKED_CLS = """
import threading

class Engine:
    def __init__(self):
        self._lock = threading.Lock()
        self.stats = []  # guarded-by: _lock

    def locked(self, x):
        with self._lock:
            self.stats.append(x)

    def {method}
"""


def test_lock_discipline_positive(tmp_path):
    findings = lint_pkg(
        tmp_path,
        {
            "bad.py": _LOCKED_CLS.format(
                method="unlocked(self, x):\n        self.stats.append(x)"
            )
        },
        passes=["lock-discipline"],
    )
    assert codes(findings) == ["GL401"]
    assert findings[0].symbol == "Engine.unlocked"


def test_lock_discipline_negative_and_init_exempt(tmp_path):
    # locked mutation + __init__-time construction are both fine
    findings = lint_pkg(
        tmp_path,
        {
            "good.py": _LOCKED_CLS.format(
                method="also_locked(self, x):\n"
                "        with self._lock:\n"
                "            self.stats.extend(x)"
            )
        },
        passes=["lock-discipline"],
    )
    assert findings == []


def test_lock_discipline_typoed_lock_name(tmp_path):
    findings = lint_pkg(
        tmp_path,
        {
            "typo.py": """
            import threading

            class Engine:
                def __init__(self):
                    self._lock = threading.Lock()
                    self.stats = []  # guarded-by: _lok
            """
        },
        passes=["lock-discipline"],
    )
    assert codes(findings) == ["GL402"]


def test_lock_discipline_deep_chain_and_augassign(tmp_path):
    findings = lint_pkg(
        tmp_path,
        {
            "deep.py": """
            import threading

            class Stats:
                total = 0.0

            class Engine:
                def __init__(self):
                    self._lock = threading.Lock()
                    self.stats = Stats()  # guarded-by: _lock

                def bad(self, dt):
                    self.stats.total += dt

                def good(self, dt):
                    with self._lock:
                        self.stats.total += dt
            """
        },
        passes=["lock-discipline"],
    )
    assert codes(findings) == ["GL401"]
    assert findings[0].detail == "self.stats.total:augassign"


# ---------------------------------------------------------------------------
# thread-escape (GL403/404) and the thread-root set
# ---------------------------------------------------------------------------


def test_thread_roots_discovered_through_self_method_submit_and_partial(tmp_path):
    """Thread(target=self._loop), executor.submit(partial(f, x)), and a
    respawn path (a thread root that re-spawns itself, the async_rl actor
    shape) all land in the callgraph's thread-root set."""
    root = tmp_path / "pkg"
    root.mkdir()
    (root / "__init__.py").write_text("")
    (root / "mod.py").write_text(textwrap.dedent("""
        import threading
        from functools import partial

        def job(x):
            return x + 1

        class Engine:
            def start(self, executor):
                t = threading.Thread(target=self._loop)
                t.start()
                executor.submit(partial(job, 2))

            def _loop(self):
                while True:
                    self._respawn()

            def _respawn(self):
                threading.Thread(target=self._loop).start()
        """))
    ctx = AnalysisContext(str(root))
    roots = {(r.fn.qualname, r.via) for r in ctx.callgraph.thread_roots}
    assert ("Engine._loop", "Thread") in roots
    assert ("job", "submit") in roots
    membership = ctx.callgraph.thread_membership()
    # the respawn helper is reachable from the _loop root (labels are the
    # root FunctionInfo.full, so same-named roots in different modules
    # stay distinct)
    full = next(
        f.full for f in ctx.callgraph.functions if f.qualname == "Engine._respawn"
    )
    assert any(label.endswith("Engine._loop") for label in membership[full])


def test_thread_roots_on_real_tree_cover_async_and_pipeline():
    """The real tree's actor/worker spawn points stay discovered (guards
    against the escape analysis going vacuous after a refactor)."""
    ctx = AnalysisContext(TREE)
    roots = {r.fn.qualname for r in ctx.callgraph.thread_roots}
    assert "AsyncCollector._actor_main" in roots  # incl. the respawn path
    assert "RolloutPipeline._worker_loop" in roots
    assert any("work" in r for r in roots)  # the PPO pipeline submit closures
    membership = ctx.callgraph.thread_membership()
    # the dispatcher helpers run on the actor root, not main
    spec_fn = next(
        f.full for f in ctx.callgraph.functions
        if f.qualname == "AsyncCollector._next_spec"
    )
    assert any("_actor_main" in r for r in membership[spec_fn])


_ESCAPE_PKG = {
    "esc.py": """
    import threading

    class Pipe:
        def __init__(self):
            self.total = 0.0
            self.started = False

        def start(self):
            self.started = True
            threading.Thread(target=self._worker).start()

        def _worker(self):
            self.total += 1.0      # unguarded cross-thread write

        def read(self):
            return self.total      # main-thread read of the same attr
    """
}


def test_thread_escape_unguarded_cross_thread_write(tmp_path):
    findings = lint_pkg(tmp_path, _ESCAPE_PKG, passes=["thread-escape"])
    assert codes(findings) == ["GL403"]
    assert findings[0].detail == "total"
    assert findings[0].symbol == "Pipe"


def test_thread_escape_negatives(tmp_path):
    # locked both sides (annotated), init-only writes, single-root attrs,
    # and sync-primitive method calls are all clean
    findings = lint_pkg(
        tmp_path,
        {
            "good.py": """
            import threading

            class Pipe:
                def __init__(self):
                    self._lock = threading.Lock()
                    self._stop = threading.Event()
                    self.total = 0.0  # guarded-by: _lock
                    self.config = {"depth": 2}

                def start(self):
                    threading.Thread(target=self._worker).start()

                def _worker(self):
                    while not self._stop.is_set():
                        with self._lock:
                            self.total += 1.0

                def read(self):
                    with self._lock:
                        return self.total + self.config["depth"]

                def close(self):
                    self._stop.set()

                def main_only(self):
                    self.tally = 1.0     # written+read on main only
                    return self.tally
            """
        },
        passes=["thread-escape"],
    )
    assert findings == []


def test_thread_escape_annotated_attr_unlocked_read(tmp_path):
    findings = lint_pkg(
        tmp_path,
        {
            "read.py": """
            import threading

            class Pipe:
                def __init__(self):
                    self._lock = threading.Lock()
                    self.total = 0.0  # guarded-by: _lock

                def start(self):
                    threading.Thread(target=self._worker).start()

                def _worker(self):
                    with self._lock:
                        self.total += 1.0

                def read(self):
                    return self.total       # cross-thread read, no lock
            """
        },
        passes=["thread-escape"],
    )
    assert [(f.code, f.detail) for f in findings] == [("GL403", "total:read")]
    assert findings[0].symbol == "Pipe.read"


def test_thread_escape_closure_rebind(tmp_path):
    findings = lint_pkg(
        tmp_path,
        {
            "rebind.py": """
            import threading

            def collect(executor, items):
                total = 0.0
                def work():
                    nonlocal total
                    total += 1.0        # races the submitting frame
                for _ in items:
                    executor.submit(work)
                return total
            """
        },
        passes=["thread-escape"],
    )
    assert ("GL404", "total") in [(f.code, f.detail) for f in findings]


def test_thread_escape_shared_helper_keeps_main_membership(tmp_path):
    """A helper reachable from a thread root AND called by main-side code
    carries both labels — the race through the shared helper is a finding,
    not worker-private state."""
    findings = lint_pkg(
        tmp_path,
        {
            "shared.py": """
            import threading

            class Acc:
                def start(self):
                    threading.Thread(target=self._worker).start()

                def _worker(self):
                    self._bump()

                def _bump(self):
                    self.count = 1.0

                def main_loop(self):
                    self._bump()
                    return self.count
            """
        },
        passes=["thread-escape"],
    )
    assert ("GL403", "count") in [(f.code, f.detail) for f in findings]


def test_thread_escape_worker_private_state_is_clean(tmp_path):
    """The spawn-site reference (`Thread(target=...)` / `submit(work)`)
    must NOT give the root function main membership: state touched only
    inside the worker body is single-root."""
    findings = lint_pkg(
        tmp_path,
        {
            "private.py": """
            import threading

            class Counter:
                def start(self):
                    def work():
                        self.ticks = 1.0
                        return self.ticks       # worker-private
                    threading.Thread(target=work).start()
            """
        },
        passes=["thread-escape"],
    )
    assert findings == []


def test_thread_escape_default_args_belong_to_spawner(tmp_path):
    # `def work(fn=self._x)` evaluates on the MAIN thread at def time:
    # not a cross-thread read (the real flops-thread pattern)
    findings = lint_pkg(
        tmp_path,
        {
            "defaults.py": """
            import threading

            class T:
                def setup(self):
                    self._fn = lambda: 1

                def go(self):
                    def work(fn=self._fn):
                        return fn()
                    threading.Thread(target=work).start()
            """
        },
        passes=["thread-escape"],
    )
    assert findings == []


# ---------------------------------------------------------------------------
# collective-discipline (GL701–GL704)
# ---------------------------------------------------------------------------


def test_gl701_rank_guarded_collective(tmp_path):
    findings = lint_pkg(
        tmp_path,
        {
            "bad.py": """
            import jax
            import numpy as np
            from jax.experimental import multihost_utils

            def exchange(flag):
                if jax.process_index() == 0:
                    # only rank 0 posts: every other rank hangs it
                    return multihost_utils.process_allgather(np.asarray(flag))
                return None
            """
        },
        passes=["collective-discipline"],
    )
    assert codes(findings) == ["GL701"]
    assert findings[0].detail == "process_allgather"


def test_gl701_through_predicate_local_and_early_return(tmp_path):
    findings = lint_pkg(
        tmp_path,
        {
            "deep.py": """
            import jax
            from jax.experimental import multihost_utils

            def _is_primary():
                return jax.process_index() == 0

            def barrier(name):
                multihost_utils.sync_global_devices(name)

            def commit_guarded():
                primary = _is_primary()
                if primary:
                    barrier("inside_guard")   # bearing call under rank guard

            def commit_early_exit():
                if _is_primary():
                    return
                barrier("after_exit")         # only non-primary ranks arrive
            """
        },
        passes=["collective-discipline"],
    )
    assert codes(findings) == ["GL701", "GL701"]
    assert {f.symbol for f in findings} == {"commit_guarded", "commit_early_exit"}


def test_gl701_negative_barrier_paired_primary_commit(tmp_path):
    """The legitimate checkpoint-commit shape: rank 0 authors host-side
    files INSIDE the guard, the barrier stays OUTSIDE — every rank posts
    the collective, so nothing fires."""
    findings = lint_pkg(
        tmp_path,
        {
            "good.py": """
            import json
            import jax
            from jax.experimental import multihost_utils

            def _is_primary():
                return jax.process_index() == 0

            def commit(directory):
                if _is_primary():
                    with open(directory + "/marker", "w") as f:
                        json.dump({"ok": True}, f)
                multihost_utils.sync_global_devices(directory)

            def uniform_gate(x):
                # process_count is identical on every rank: not a rank guard
                if jax.process_count() > 1:
                    return multihost_utils.process_allgather(x)
                return x
            """
        },
        passes=["collective-discipline"],
    )
    assert findings == []


def test_gl702_per_rank_loop_trip_count(tmp_path):
    findings = lint_pkg(
        tmp_path,
        {
            "loops.py": """
            import os
            import jax
            from jax.experimental import multihost_utils

            def bad(spool):
                for name in os.listdir(spool):     # per-rank directory state
                    multihost_utils.sync_global_devices(name)

            def bad_local(reqs, x):
                # a bare local hides its per-rank provenance: not uniform
                pending = [r for r in reqs if r.rank == jax.process_index()]
                for p in pending:
                    multihost_utils.process_allgather(p)

            def good(config, x):
                for _ in range(config.train.epochs):   # uniform by contract
                    multihost_utils.process_allgather(x)
            """
        },
        passes=["collective-discipline"],
    )
    assert codes(findings) == ["GL702", "GL702"]
    assert {f.symbol for f in findings} == {"bad", "bad_local"}


def test_gl703_duplicated_barrier_literal(tmp_path):
    findings = lint_pkg(
        tmp_path,
        {
            "names.py": """
            from jax.experimental import multihost_utils

            def save():
                multihost_utils.sync_global_devices("ckpt_edge")

            def restore():
                multihost_utils.sync_global_devices("ckpt_edge")

            def unique():
                multihost_utils.sync_global_devices("only_here")
            """
        },
        passes=["collective-discipline"],
    )
    assert codes(findings) == ["GL703", "GL703"]
    assert all(f.detail == "ckpt_edge" for f in findings)
    # ...including through a parameter-forwarding wrapper
    findings = lint_pkg(
        tmp_path,
        {
            "wrap.py": """
            from jax.experimental import multihost_utils

            def barrier(name):
                multihost_utils.sync_global_devices(f"pkg_{name}")

            def one():
                barrier("edge")

            def two():
                barrier("edge")
            """
        },
        passes=["collective-discipline"],
        name="pkg2",
    )
    assert codes(findings) == ["GL703", "GL703"]


def test_gl704_config_gated_collective(tmp_path):
    findings = lint_pkg(
        tmp_path,
        {
            "gated.py": """
            from jax.experimental import multihost_utils

            def boundary(config, flag):
                if config.resilience.exchange_flags:   # unregistered field
                    multihost_utils.process_allgather(flag)
                if config.resilience.coordinate_preemption:  # registered
                    multihost_utils.process_allgather(flag)
            """
        },
        passes=["collective-discipline"],
    )
    assert [(f.code, f.detail) for f in findings] == [
        ("GL704", "exchange_flags->process_allgather")
    ]


def test_rank_uniform_registry_matches_real_gates():
    """The registered contract fields stay declared on the real config
    dataclasses (a renamed knob must re-justify its registry entry)."""
    from trlx_tpu.analysis.collectives import RANK_UNIFORM_FIELDS
    from trlx_tpu.analysis.conventions import ConfigKeysPass

    sections = ConfigKeysPass()._collect_sections(AnalysisContext(TREE))
    declared = set().union(*sections.values())
    missing = RANK_UNIFORM_FIELDS - declared
    assert not missing, f"registered rank-uniform fields not on any config: {missing}"


# ---------------------------------------------------------------------------
# ownership/lifecycle (GL801–GL804) and the acquire/release registry
# ---------------------------------------------------------------------------

_POOL = """
class Pool:
    def alloc(self, n):  # acquires: block-ref
        return list(range(n))

    def release(self, blocks):  # releases: block-ref(arg)
        return blocks
"""

_LEAK_PKG = {
    "leak.py": _POOL + """
def leak_on_error(pool: Pool, n, bad):
    blocks = pool.alloc(n)
    if bad:
        raise RuntimeError("boom")      # GL801: blocks leak on this edge
    table = {}
    table[0] = blocks                    # ownership transferred
    return table
"""
}

_DOUBLE_RELEASE_PKG = {
    "dbl.py": _POOL + """
def double(pool: Pool, n):
    blocks = pool.alloc(n)
    pool.release(blocks)
    pool.release(blocks)                 # GL802
"""
}


def test_ownership_leak_on_exception_path(tmp_path):
    findings = lint_pkg(tmp_path, _LEAK_PKG, passes=["ownership"])
    assert [(f.code, f.detail) for f in findings] == [("GL801", "blocks:block-ref")]
    assert findings[0].symbol == "leak_on_error"


def test_ownership_leak_on_early_return_and_function_end(tmp_path):
    findings = lint_pkg(
        tmp_path,
        {
            "ret.py": _POOL + """
def early(pool: Pool, n, flag):
    blocks = pool.alloc(n)
    if flag:
        return 0                        # GL801: early return, blocks live
    pool.release(blocks)
    return 1

def drops(pool: Pool, n):
    blocks = pool.alloc(n)              # GL801 at function end
    print(len(blocks))
"""
        },
        passes=["ownership"],
    )
    assert codes(findings) == ["GL801", "GL801"]
    assert {f.symbol for f in findings} == {"early", "drops"}


def test_ownership_discarded_handle(tmp_path):
    findings = lint_pkg(
        tmp_path,
        {
            "disc.py": _POOL + """
def discard(pool: Pool):
    pool.alloc(3)                       # result dropped: nothing can release
"""
        },
        passes=["ownership"],
    )
    assert [(f.code, f.detail) for f in findings] == [
        ("GL801", "<discarded>:block-ref")
    ]


def test_ownership_double_release(tmp_path):
    findings = lint_pkg(tmp_path, _DOUBLE_RELEASE_PKG, passes=["ownership"])
    assert [(f.code, f.detail) for f in findings] == [("GL802", "blocks:block-ref")]


def test_ownership_use_after_release(tmp_path):
    findings = lint_pkg(
        tmp_path,
        {
            "uar.py": _POOL + """
def use_after(pool: Pool, n):
    blocks = pool.alloc(n)
    pool.release(blocks)
    return blocks[0]                    # GL803
"""
        },
        passes=["ownership"],
    )
    assert [(f.code, f.detail) for f in findings] == [("GL803", "blocks:block-ref")]


def test_ownership_conditional_release(tmp_path):
    findings = lint_pkg(
        tmp_path,
        {
            "cond.py": _POOL + """
def cond_release(pool: Pool, n, ok):
    blocks = pool.alloc(n)
    if ok:
        pool.release(blocks)
    return None                         # GL804: other branch leaks
"""
        },
        passes=["ownership"],
    )
    assert [(f.code, f.detail) for f in findings] == [("GL804", "blocks:block-ref")]


def test_ownership_negatives_finally_with_and_transfer(tmp_path):
    # finally-covered exits, with-context acquires, the error-path-release-
    # then-main-path-transfer shape (the engine's _prepare_row), and
    # object-scoped (attr receiver / "(object)" spec) calls are all clean
    findings = lint_pkg(
        tmp_path,
        {
            "ok.py": _POOL + """
class Tracer:
    def span(self, name):  # acquires: span
        return name

class Cache:
    def insert(self, pool, blocks):  # acquires: entry-ref(object)
        return len(blocks)

def covered(pool: Pool, n):
    blocks = pool.alloc(n)
    try:
        x = blocks[0]
        return x                         # covered by the finally below
    finally:
        pool.release(blocks)

def error_path_counterpart(pool: Pool, store, n, shared):
    pool.release(shared)
    blocks = pool.alloc(n)
    try:
        more = pool.alloc(n)
    except RuntimeError:
        pool.release(blocks)             # error-path release...
        raise
    store[0] = blocks + more             # ...main path transfers ownership

def spans(tracer: Tracer):
    with tracer.span("engine/x"):
        pass

def object_scoped(pool: Pool, cache: Cache, n):
    cache.insert(pool, [1, 2])           # (object) spec: cache owns the refs
"""
        },
        passes=["ownership"],
    )
    assert findings == [], [f.render() for f in findings]


def test_ownership_thread_pair(tmp_path):
    findings = lint_pkg(
        tmp_path,
        {
            "thr.py": """
import threading

def f():
    pass

def joined():
    t = threading.Thread(target=f)
    t.start()
    t.join()

def stored(bag):
    t = threading.Thread(target=f)
    bag.append(t)                        # ownership moved BEFORE start
    t.start()

def leaked(flag):
    t = threading.Thread(target=f)
    t.start()
    if flag:
        return                           # GL801: t live on this exit
    t.join()
"""
        },
        passes=["ownership"],
    )
    assert [(f.code, f.symbol, f.detail) for f in findings] == [
        ("GL801", "leaked", "t:thread")
    ]


def test_ownership_registry_on_real_tree():
    """The seeded acquire/release pairs stay annotated (guards against the
    pass going vacuous after a refactor): allocator refs, the engine's
    alloc wrapper and row refs, prefix-cache entries, spool chunks,
    checkpoint staging, tracer spans."""
    from trlx_tpu.analysis.ownership import OwnershipRegistry

    ctx = AnalysisContext(TREE)
    reg = OwnershipRegistry(ctx.callgraph)
    triples = {
        (pm.fn.qualname, pm.role, pm.resource)
        for pms in reg.by_name.values()
        for pm in pms
    }
    assert ("BlockAllocator.alloc", "acquires", "kv-block-ref") in triples
    assert ("BlockAllocator.retain", "acquires", "kv-block-ref") in triples
    assert ("BlockAllocator.release", "releases", "kv-block-ref") in triples
    assert ("ContinuousEngine._alloc_blocks", "acquires", "kv-block-ref") in triples
    assert ("ContinuousEngine._prepare_row", "acquires", "row-block-ref") in triples
    assert ("ContinuousEngine._harvest", "releases", "row-block-ref") in triples
    assert ("PrefixCache.insert", "acquires", "prefix-entry-ref") in triples
    assert ("PrefixCache.evict", "releases", "prefix-entry-ref") in triples
    assert ("FileExperienceQueue.put", "acquires", "spool-chunk") in triples
    assert ("FileExperienceQueue.get", "releases", "spool-chunk") in triples
    assert ("save_state", "acquires", "ckpt-staging") in triples
    assert ("save_state.<locals>.commit", "releases", "ckpt-staging") in triples
    assert ("Tracer.span", "acquires", "span") in triples


# ---------------------------------------------------------------------------
# determinism discipline (GL901–GL904) and the bit-equivalence root set
# ---------------------------------------------------------------------------

_TIME_STORE_PKG = {
    "det_time.py": """
import time

def make_experience(store):
    store.append(time.time())            # GL901: wall clock into the store
"""
}

_UNSORTED_SCAN_PKG = {
    "det_scan.py": """
import os

def committed_indices(spool):
    out = set()
    for name in os.listdir(spool):       # GL903: unsorted spool scan
        out.add(name)
    return out

class FileExperienceQueue:
    def put(self, spool):
        return committed_indices(spool)
"""
}


def test_determinism_wall_clock_and_rng(tmp_path):
    findings = lint_pkg(
        tmp_path,
        {
            **_TIME_STORE_PKG,
            "det_rng.py": """
import random
import numpy as np

def _collect_serial(batch):
    random.shuffle(batch)                # GL902: module-level RNG
    return batch + [np.random.rand()]    # GL902: unseeded global np RNG
""",
        },
        passes=["determinism"],
    )
    assert [(f.code, f.detail) for f in findings] == [
        ("GL902", "random.shuffle"),
        ("GL902", "numpy.random.rand"),
        ("GL901", "time.time"),
    ]


def test_determinism_unsorted_scan_and_set_iteration(tmp_path):
    findings = lint_pkg(
        tmp_path,
        {
            **_UNSORTED_SCAN_PKG,
            "det_set.py": """
def export_history(rows):
    seen = {r for r in rows}
    out = []
    for r in seen:                       # GL904: salted set order
        out.append(r)
    return out
""",
        },
        passes=["determinism"],
    )
    assert [(f.code, f.detail) for f in findings] == [
        ("GL903", "os.listdir"),
        ("GL904", "seen"),
    ]


def test_determinism_negatives(tmp_path):
    # sorted() at the call site, seeded generator instances, perf_counter
    # intervals, order-free consumers (len/membership), and nondeterminism
    # OUTSIDE the root-reachable set are all clean
    findings = lint_pkg(
        tmp_path,
        {
            "ok.py": """
import os
import random
import time
import numpy as np

def make_experience(root, rows):
    names = sorted(os.listdir(root))
    rng = np.random.default_rng(0)
    jitter = random.Random(1).random()
    t0 = time.perf_counter()
    seen = {r for r in rows}
    count = len({n for n in names})
    ordered = sorted(seen)
    return names, rng, jitter, time.perf_counter() - t0, ordered, count

def host_tool(root):
    # not reachable from any bit-equivalence root: out of scope
    return os.listdir(root), time.time()
"""
        },
        passes=["determinism"],
    )
    assert findings == [], [f.render() for f in findings]


def test_determinism_reaches_through_calls(tmp_path):
    # the scan lives in a helper: reachability from the root finds it
    findings = lint_pkg(tmp_path, _UNSORTED_SCAN_PKG, passes=["determinism"])
    assert [(f.code, f.symbol) for f in findings] == [
        ("GL903", "committed_indices")
    ]
    assert "FileExperienceQueue.put" in findings[0].message


def test_determinism_set_rebound_to_sorted_is_clean(tmp_path):
    # `seen = sorted(seen)` launders the set into a list: iterating the
    # rebound name must NOT fire GL904 (review finding: the set-local
    # tracker never cleared on non-set reassignment)
    findings = lint_pkg(
        tmp_path,
        {
            "rebind.py": """
def export_history(rows):
    seen = {r for r in rows}
    seen = sorted(seen)
    out = []
    for r in seen:
        out.append(r)
    return out
"""
        },
        passes=["determinism"],
    )
    assert findings == [], [f.render() for f in findings]


def test_determinism_rng_not_exempted_in_telemetry_modules(tmp_path):
    # TIMESTAMP_EXEMPT_PATHS exempts wall-clock reads ONLY: global RNG on a
    # bit-critical path is a divergence wherever it lives (review finding:
    # the GL902 branch was gated on the clock exemption). Fixture packages
    # never match the trlx_tpu/ path prefixes, so assert the rule directly:
    # a module whose clock reads ARE exempt must still flag RNG.
    import trlx_tpu.analysis.determinism as det

    root = tmp_path / "pkg"
    root.mkdir()
    (root / "__init__.py").write_text("")
    (root / "tele.py").write_text(textwrap.dedent("""
        import random
        import time

        def make_experience(store):
            store.append(time.time())
            random.shuffle(store)
        """))
    ctx = AnalysisContext(str(root))
    orig = det.TIMESTAMP_EXEMPT_PATHS
    det.TIMESTAMP_EXEMPT_PATHS = ("pkg/",)
    try:
        findings = det.DeterminismPass().run(ctx)
    finally:
        det.TIMESTAMP_EXEMPT_PATHS = orig
    assert [(f.code, f.detail) for f in findings] == [
        ("GL902", "random.shuffle")
    ]


def test_ownership_events_in_if_condition(tmp_path):
    # releases/reads spelled in an `if` TEST run on every path and must be
    # seen (review finding: the walk recursed into branches without
    # scanning the condition, unlike For/While/With headers)
    findings = lint_pkg(
        tmp_path,
        {
            "iftest.py": _POOL + """
def dbl_in_test(pool: Pool, n):
    b = pool.alloc(n)
    pool.release(b)
    if pool.release(b):                  # GL802 in the condition
        return 1
    return 0

def read_in_test(pool: Pool, n):
    b = pool.alloc(n)
    pool.release(b)
    if b:                                # GL803 in the condition
        return 1
    return 0
"""
        },
        passes=["ownership"],
    )
    assert [(f.code, f.symbol) for f in findings] == [
        ("GL802", "dbl_in_test"),
        ("GL803", "read_in_test"),
    ]


def test_determinism_root_set_on_real_tree():
    """The bit-equivalence-critical root set stays resolved and closed over
    the real tree (guards against the pass going vacuous): collection
    paths, the spool protocol, checkpoint save/restore incl. the nested
    commit closure, and FaultPlan parsing."""
    from trlx_tpu.analysis.determinism import BIT_EQUIVALENCE_ROOTS

    ctx = AnalysisContext(TREE)
    g = ctx.callgraph
    roots = g.resolve_root_names(BIT_EQUIVALENCE_ROOTS)
    quals = {r.qualname for r in roots}
    assert "PPOTrainer.make_experience" in quals
    assert "FileExperienceQueue.put" in quals
    assert "save_state" in quals
    assert "FaultPlan.parse" in quals
    assert "PPORolloutStorage.export_history" in quals
    reach = g.reach_from(roots)
    # GRPO collects through PPOTrainer.make_experience: its part of the
    # ordered finalize is reached as an override of the collector's hooks
    assert any(f.endswith("GRPOTrainer._chunk_element_fn") for f in reach)
    assert any(f.endswith("GRPOTrainer._collection_summary") for f in reach)
    assert any(f.endswith("save_state.<locals>.commit") for f in reach)
    assert any("_checkpoint_step_dirs" in f for f in reach)
    assert len(reach) >= 40
    # the serve KV re-land paths (PR 19): host-tier re-land and both
    # preemption seams promise "re-landed prefix == cold prefill", so
    # their closures must stay free of iteration-order / wall-clock /
    # unsorted-scan hazards
    assert "HostTier.reland_many" in quals
    assert "ContinuousEngine._reland_from_tier" in quals
    assert "ContinuousEngine._preempt_slot" in quals
    assert "ContinuousEngine._preempt_for_priority" in quals


# ---------------------------------------------------------------------------
# kernel discipline (GL1001–GL1004)
# ---------------------------------------------------------------------------

# Fixture packages route their gate through a module NAMED pallas_utils —
# the pass matches the trailing `pallas_utils.<gate>` of the resolved
# name, so a mini-tree earns a clean bill the same way ops/ does. Fixtures
# that want GL1004 quiet register under the real `flash-fwd`/`flash-bwd`
# rows: entry `flash_attention`(+`_bwd_chunk`), reference
# `attention_reference`, and a `tests/test_flash_attention.py` created
# next to the package root (ctx.base).

_PALLAS_UTILS_FIXTURE = """
def has_pallas_tpu():
    return False
"""


def _touch_parity_test(tmp_path):
    tests = tmp_path / "tests"
    tests.mkdir(exist_ok=True)
    (tests / "test_flash_attention.py").write_text("")


def test_kernel_gate_ungated_entry(tmp_path):
    """GL1001 positive: a pallas_call whose upward caller closure never
    crosses the pallas_utils gate names each ungated entry."""
    _touch_parity_test(tmp_path)
    findings = lint_pkg(
        tmp_path,
        {
            "kern.py": """
            from jax.experimental import pallas as pl

            def _kernel(x_ref, o_ref):
                o_ref[...] = x_ref[...]

            def attention_reference(x):
                return x

            def flash_attention(x):
                return pl.pallas_call(_kernel, out_shape=x)(x)

            def flash_attention_bwd_chunk(x):
                return pl.pallas_call(_kernel, out_shape=x)(x)
            """
        },
        passes=["kernel-discipline"],
    )
    assert codes(findings) == ["GL1001", "GL1001"]
    assert {(f.symbol, f.detail) for f in findings} == {
        ("flash_attention", "flash_attention"),
        ("flash_attention_bwd_chunk", "flash_attention_bwd_chunk"),
    }
    assert "Mosaic-less build" in findings[0].message


_GATED_KERNEL_PKG = {
    "pallas_utils.py": _PALLAS_UTILS_FIXTURE,
    "kern.py": """
    from jax.experimental import pallas as pl
    from pkg.pallas_utils import has_pallas_tpu

    def _kernel(x_ref, o_ref):
        o_ref[...] = x_ref[...]

    def attention_reference(x):
        return x

    def flash_attention(x):
        if not has_pallas_tpu():
            return attention_reference(x)
        return pl.pallas_call(_kernel, out_shape=x)(x)

    def flash_attention_bwd_chunk(x):
        if not has_pallas_tpu():
            return attention_reference(x)
        return pl.pallas_call(_kernel, out_shape=x)(x)
    """,
}


def test_kernel_gate_negative_gated_entry(tmp_path):
    """GL1001/GL1003/GL1004 negative: gate-bearing entries, a pure kernel,
    and registered flavors with a live reference and parity test file."""
    _touch_parity_test(tmp_path)
    findings = lint_pkg(tmp_path, _GATED_KERNEL_PKG, passes=["kernel-discipline"])
    assert findings == []


def test_kernel_gate_stitches_custom_vjp_rules(tmp_path):
    """The defvjp stitch: fwd/bwd rules have no syntactic caller, but a
    module-level `primal.defvjp(fwd, bwd)` makes the primal their caller,
    so rules inherit the primal's gate instead of surfacing as ungated
    roots. This is the fix for the six false positives the real tree's
    custom_vjp pairs (flash fwd/bwd, fused-loss iw/noiw) would otherwise
    produce."""
    _touch_parity_test(tmp_path)
    findings = lint_pkg(
        tmp_path,
        {
            "pallas_utils.py": _PALLAS_UTILS_FIXTURE,
            "kern.py": """
            import jax
            from jax.experimental import pallas as pl
            from pkg.pallas_utils import has_pallas_tpu

            def _kernel(x_ref, o_ref):
                o_ref[...] = x_ref[...]

            def attention_reference(x):
                return x

            @jax.custom_vjp
            def _flash(x):
                return pl.pallas_call(_kernel, out_shape=x)(x)

            def _fwd(x):
                return pl.pallas_call(_kernel, out_shape=x)(x), x

            def _bwd(res, g):
                return (pl.pallas_call(_kernel, out_shape=g)(g),)

            _flash.defvjp(_fwd, _bwd)

            def flash_attention(x):
                if not has_pallas_tpu():
                    return attention_reference(x)
                return _flash(x)

            def flash_attention_bwd_chunk(x):
                return flash_attention(x)
            """,
        },
        passes=["kernel-discipline"],
    )
    assert findings == []


_LITERAL_STAMP_PKG = {
    "stamp.py": """
    import jax.numpy as jnp

    def publish(gauges, metrics):
        gauges["decode_pallas"] = 1.0
        metrics.gauges["prefill_pallas"] = float(True)
        metrics.record(loss_kernel_pallas=jnp.asarray(1))
        return {"sample_pallas": 1}
    """
}


def test_kernel_gauge_literal_stamps(tmp_path):
    """GL1002 positive: every *_pallas store shape (subscript, attribute
    chain, keyword, dict literal) stamped from a truthy literal — wrapper
    calls like float(True)/jnp.asarray(1) don't launder it."""
    findings = lint_pkg(tmp_path, _LITERAL_STAMP_PKG, passes=["kernel-discipline"])
    assert codes(findings) == ["GL1002"] * 4
    assert sorted(f.detail for f in findings) == [
        "decode_pallas", "loss_kernel_pallas", "prefill_pallas",
        "sample_pallas",
    ]
    assert all("twice-shipped" in f.message for f in findings)


def test_kernel_gauge_stamp_negatives(tmp_path):
    """GL1002 negative: values derived from has_pallas_tpu(), falsy
    literal defaults (the pre-gate placeholder), and AnnAssign field
    declarations are all fine."""
    findings = lint_pkg(
        tmp_path,
        {
            "pallas_utils.py": _PALLAS_UTILS_FIXTURE,
            "stamp.py": """
            from pkg.pallas_utils import has_pallas_tpu

            class Stats:
                decode_pallas: float = 0.0

            def publish(gauges):
                use = has_pallas_tpu()
                gauges["decode_pallas"] = float(use)
                gauges["prefill_pallas"] = 0.0
                return {"sample_pallas": 1.0 if use else 0.0}
            """,
        },
        passes=["kernel-discipline"],
    )
    assert findings == []


_IMPURE_KERNEL_PKG = {
    "pallas_utils.py": _PALLAS_UTILS_FIXTURE,
    "kern.py": """
    import time
    import numpy as np
    from jax.experimental import pallas as pl
    from pkg.pallas_utils import has_pallas_tpu

    TABLE = np.arange(128)
    OFFS = np.zeros(4)

    def _kernel(x_ref, o_ref):
        t = time.time()
        o_ref[...] = x_ref[...] * TABLE + t

    def attention_reference(x):
        return x

    def flash_attention(x):
        if not has_pallas_tpu():
            return attention_reference(x)
        spec = pl.BlockSpec((8, 128), lambda i: (OFFS, 0))
        return pl.pallas_call(_kernel, out_shape=x, in_specs=[spec])(x)

    def flash_attention_bwd_chunk(x):
        return flash_attention(x)
    """,
}


def test_kernel_purity_positive(tmp_path):
    """GL1003 positive: a wall-clock read and an ndarray closure in the
    kernel body, and an ndarray closure in a BlockSpec index map."""
    _touch_parity_test(tmp_path)
    findings = lint_pkg(tmp_path, _IMPURE_KERNEL_PKG, passes=["kernel-discipline"])
    assert codes(findings) == ["GL1003"] * 3
    by_detail = {f.detail: f for f in findings}
    assert set(by_detail) == {"time.time", "TABLE", "OFFS"}
    assert by_detail["TABLE"].symbol == "_kernel"
    assert "lambda" in by_detail["OFFS"].symbol  # the index map
    assert "constant fold" in by_detail["TABLE"].message


def test_kernel_purity_negatives(tmp_path):
    """GL1003 negative: scalar closures (block sizes, NEG_INF-style
    imported constants), package helper calls, and index maps that are
    pure over grid indices + captured ints are all fine."""
    _touch_parity_test(tmp_path)
    findings = lint_pkg(
        tmp_path,
        {
            "pallas_utils.py": """
            NEG_INF = -1e30

            def has_pallas_tpu():
                return False
            """,
            "kern.py": """
            import jax.numpy as jnp
            from jax.experimental import pallas as pl
            from pkg.pallas_utils import has_pallas_tpu, NEG_INF

            BLOCK = 128

            def _mask(x):
                return jnp.where(x > 0, x, NEG_INF)

            def _kernel(x_ref, o_ref):
                o_ref[...] = _mask(x_ref[...]) * BLOCK

            def attention_reference(x):
                return x

            def flash_attention(x, group=4):
                if not has_pallas_tpu():
                    return attention_reference(x)
                spec = pl.BlockSpec((BLOCK, BLOCK), lambda i, j: (i * group, j))
                return pl.pallas_call(_kernel, out_shape=x, in_specs=[spec])(x)

            def flash_attention_bwd_chunk(x):
                return flash_attention(x)
            """,
        },
        passes=["kernel-discipline"],
    )
    assert findings == []


def test_kernel_registry_unregistered_site(tmp_path):
    """GL1004 positive (a): a pallas_call whose upward closure contains
    no KERNEL_PARITY entry — a new kernel flavor with no parity story.
    (It is also an ungated entry, so GL1001 rides along.)"""
    findings = lint_pkg(
        tmp_path,
        {
            "kern.py": """
            from jax.experimental import pallas as pl

            def _kernel(x_ref, o_ref):
                o_ref[...] = x_ref[...]

            def mystery_kernel(x):
                return pl.pallas_call(_kernel, out_shape=x)(x)
            """
        },
        passes=["kernel-discipline"],
    )
    assert codes(findings) == ["GL1001", "GL1004"]
    gl1004 = [f for f in findings if f.code == "GL1004"][0]
    assert gl1004.symbol == "mystery_kernel"
    assert "KERNEL_PARITY" in gl1004.message


def test_kernel_registry_lost_reference_and_test(tmp_path):
    """GL1004 positive (b): a registered flavor present in the tree whose
    XLA reference no longer resolves and whose parity test file is gone
    surfaces one finding per lost leg."""
    findings = lint_pkg(
        tmp_path,
        {
            "pallas_utils.py": _PALLAS_UTILS_FIXTURE,
            "kern.py": """
            from jax.experimental import pallas as pl
            from pkg.pallas_utils import has_pallas_tpu

            def _kernel(x_ref, o_ref):
                o_ref[...] = x_ref[...]

            def fused_sample(x):
                if not has_pallas_tpu():
                    return x
                return pl.pallas_call(_kernel, out_shape=x)(x)
            """,
        },
        passes=["kernel-discipline"],
    )
    assert codes(findings) == ["GL1004", "GL1004"]
    assert sorted(f.detail for f in findings) == [
        "fused-sample:reference:sample_token_from_logits",
        "fused-sample:test:tests/test_paged_attention.py",
    ]


def test_kernel_parity_registry_on_real_tree():
    """The registry-vs-real-tree guard: every committed pallas_call site
    is covered by a registered flavor, every registered entry AND its XLA
    reference resolve in ops/, and every parity test file exists (guards
    against the pass going vacuous, the RANK_UNIFORM_FIELDS pattern)."""
    from trlx_tpu.analysis.kernels import KERNEL_PARITY, KernelDisciplinePass

    ctx = AnalysisContext(TREE)
    g = ctx.callgraph
    kp = KernelDisciplinePass()
    sites = kp._collect_sites(g)
    # the current kernel surface: flash fwd + fused bwd and the same pair
    # under a selection (`flash_attention(..., selection=)`, its own two
    # call sites behind the same entry), paged decode, fused sampling,
    # paged prefill, the chunked delta rule's forward
    assert len(sites) == 8, sorted(
        (s.mod.relpath, s.fn.qualname if s.fn else "<module>") for s in sites
    )
    assert {s.mod.relpath for s in sites} == {
        "trlx_tpu/ops/delta_rule.py",
        "trlx_tpu/ops/flash_attention.py",
        "trlx_tpu/ops/paged_attention.py",
        "trlx_tpu/ops/paged_prefill.py",
    }
    flavors = {flavor for flavor, _, _, _ in KERNEL_PARITY}
    assert flavors == {
        "paged-decode", "paged-prefill", "paged-verify", "fused-sample",
        "flash-fwd", "flash-bwd", "kda-scan",
    }
    for flavor, entry, reference, test_path in KERNEL_PARITY:
        assert g.resolve_root_names([entry]), f"{flavor}: entry `{entry}`"
        assert g.resolve_root_names([reference]), (
            f"{flavor}: reference `{reference}`"
        )
        assert os.path.exists(os.path.join(REPO_ROOT, test_path)), (
            f"{flavor}: parity test `{test_path}`"
        )
    # and the pass itself is silent on the committed tree
    findings, _ = run_analysis(TREE, passes=["kernel-discipline"])
    assert findings == []


def test_http_handler_thread_roots_discovered(tmp_path):
    """GL403 satellite positive: do_* methods of a BaseHTTPRequestHandler
    subclass are thread roots (ThreadingHTTPServer runs one thread per
    request) — and only do_* methods of handler subclasses."""
    root = tmp_path / "pkg"
    root.mkdir()
    (root / "__init__.py").write_text("")
    (root / "srv.py").write_text(textwrap.dedent("""
        import http.server

        class Handler(http.server.BaseHTTPRequestHandler):
            def do_GET(self):
                self.wfile.write(self.compute())

            def do_POST(self):
                self.wfile.write(b"ok")

            def compute(self):
                return b"x"

            def log_message(self, fmt, *args):
                pass

        class NotAHandler:
            def do_GET(self):
                return 1
        """))
    ctx = AnalysisContext(str(root))
    roots = {(r.fn.qualname, r.via) for r in ctx.callgraph.thread_roots}
    assert roots == {
        ("Handler.do_GET", "http-handler"),
        ("Handler.do_POST", "http-handler"),
    }


def test_http_handler_cross_request_escape(tmp_path):
    """GL403 satellite: two handler threads sharing an attr written
    outside __init__ is exactly the cross-thread escape shape — the serve
    pump-owns-engine contract is now checked, not just documented."""
    findings = lint_pkg(
        tmp_path,
        {
            "srv.py": """
            import http.server

            class Handler(http.server.BaseHTTPRequestHandler):
                def do_GET(self):
                    self.cache = self.compute()

                def do_POST(self):
                    self.wfile.write(self.cache)

                def compute(self):
                    return b"x"
            """
        },
        passes=["thread-escape"],
    )
    assert codes(findings) == ["GL403"]
    assert (findings[0].symbol, findings[0].detail) == ("Handler", "cache")


def test_http_handler_roots_on_real_tree():
    """The serve frontend's request handlers stay discovered as thread
    roots (the real-tree coverage guard for the GL403 extension)."""
    ctx = AnalysisContext(TREE)
    roots = {(r.fn.qualname, r.via) for r in ctx.callgraph.thread_roots}
    assert ("_Handler.do_GET", "http-handler") in roots
    assert ("_Handler.do_POST", "http-handler") in roots


# ---------------------------------------------------------------------------
# metric-names (GL501) and config-keys (GL601)
# ---------------------------------------------------------------------------


def test_metric_names_pass(tmp_path):
    findings = lint_pkg(
        tmp_path,
        {
            "mod.py": """
            def f(stats, metrics):
                stats["no_namespace"] = 1.0
                stats["ok/key"] = 2.0
                stats["learning_rate"] = 3.0     # frozen legacy allowlist
                metrics.inc("resilience/reward_retries")
                metrics.set_gauge("bad_gauge", 1.0)
            """
        },
        passes=["metric-names"],
    )
    assert [(f.code, f.detail) for f in findings] == [
        ("GL501", "no_namespace"),
        ("GL501", "bad_gauge"),
    ]


def test_span_names_pass(tmp_path):
    findings = lint_pkg(
        tmp_path,
        {
            "mod.py": """
            def f(self, tracer, name):
                with self.obs.span("bad span name"):
                    pass
                with self.obs.span("rollout"):        # frozen legacy allowlist
                    pass
                with tracer.span("engine/queue_wait"):  # namespaced: ok
                    pass
                with self._span(
                    "also_bad", live=3                # multi-line call: caught
                ):
                    pass
                tracer.instant("not_a_span")          # no such method since PR 35: not scanned
                tracer.add_complete_event("bad_event", 0.0, 1.0)
                tracer.add_complete_event("engine/prefill", 0.0, 1.0)
                with tracer.span(name):               # dynamic: out of scope
                    pass
                with tracer.span(f"{{name}}/x"):        # f-string: out of scope
                    pass
            """
        },
        passes=["span-names"],
    )
    assert [(f.code, f.detail) for f in findings] == [
        ("GL502", "bad span name"),
        ("GL502", "also_bad"),
        ("GL502", "bad_event"),
    ]


def test_span_names_legacy_allowlist_is_exact():
    from trlx_tpu.analysis.conventions import LEGACY_SPAN_NAMES

    # frozen: the five pre-convention trainer spans, nothing else. Adding
    # here instead of namespacing a new span is a review error.
    assert LEGACY_SPAN_NAMES == {
        "rollout", "generate", "score", "reward", "train_step",
    }


_CONFIG_FILES = {
    "configs.py": """
    from dataclasses import dataclass

    @dataclass
    class MethodConfig:
        name: str = "m"

    @dataclass
    class PPOConfig(MethodConfig):
        chunk_size: int = 16

    @dataclass
    class TrainConfig:
        batch_size: int = 1
        seq_length: int = 64

    @dataclass
    class TRLConfig:
        method: MethodConfig
        train: TrainConfig
    """,
}


def test_config_keys_pass(tmp_path):
    findings = lint_pkg(
        tmp_path,
        {
            **_CONFIG_FILES,
            "uses.py": """
            def f(config):
                ok = config.train.batch_size + config.method.chunk_size
                bad = config.train.batch_sizee
                also_ok = self_unrelated.train.whatever  # receiver not a config
                return ok, bad
            """,
        },
        passes=["config-keys"],
    )
    assert [(f.code, f.detail) for f in findings] == [
        ("GL601", "train.batch_sizee")
    ]


def test_config_keys_on_real_configs():
    # the real dataclasses are collected (guards against the pass going
    # vacuous after a configs.py refactor)
    from trlx_tpu.analysis.conventions import ConfigKeysPass

    ctx = AnalysisContext(TREE)
    sections = ConfigKeysPass()._collect_sections(ctx)
    assert "rollout_pipeline_depth" in sections["train"]
    assert "update_guard" in sections["resilience"]
    assert "chunk_size" in sections["method"]  # union over MethodConfigs
    # the engine: section (paged KV / prefix cache, docs/PERFORMANCE.md)
    # resolves like every other TRLConfig field — a typo'd engine knob
    # (config.engine.kv_blocksize) is a GL601 finding, not a silent default
    assert {"backend", "kv_block_size", "max_kv_blocks", "prefix_cache"} <= (
        sections["engine"]
    )


# ---------------------------------------------------------------------------
# baseline semantics
# ---------------------------------------------------------------------------

_VIOLATION_PKG = {
    "bad.py": """
    import jax

    def traced(x):
        return x.item()

    jax.jit(traced)
    """
}


def test_baseline_suppression_and_staleness(tmp_path):
    findings = lint_pkg(tmp_path, _VIOLATION_PKG, passes=["host-sync"])
    assert len(findings) == 1
    baseline = Baseline()
    baseline.update(findings)

    new, stale = baseline.apply(findings)
    assert new == [] and stale == []  # suppressed

    new, stale = baseline.apply([])  # the finding stopped firing
    assert new == []
    assert [e.key for e in stale] == [findings[0].key]  # stale = error

    new, stale = Baseline().apply(findings)  # entry removed
    assert new == findings  # resurfaces


def test_baseline_requires_justification(tmp_path):
    path = tmp_path / "b.txt"
    path.write_text("GL101 pkg/bad.py:traced:.item\n")  # no ' :: reason'
    with pytest.raises(BaselineError):
        Baseline.load(str(path))
    path.write_text("GL101 pkg/bad.py:traced:.item ::   \n")
    with pytest.raises(BaselineError):
        Baseline.load(str(path))
    path.write_text("GL101 pkg/bad.py:traced:.item :: fenced, once per step\n")
    assert len(Baseline.load(str(path)).entries) == 1


def test_cli_exit_codes(tmp_path):
    root = tmp_path / "pkg"
    root.mkdir()
    (root / "__init__.py").write_text("")
    (root / "bad.py").write_text(textwrap.dedent(_VIOLATION_PKG["bad.py"]))

    assert main([str(root), "--no-baseline"]) == 1  # violation

    findings, _ = run_analysis(str(root), passes=["host-sync"])
    good = tmp_path / "good_baseline.txt"
    b = Baseline()
    b.update(findings)
    for e in b.entries.values():
        e.justification = "fixture: intentional"
    b.save(str(good))
    assert main([str(root), "--baseline", str(good)]) == 0  # suppressed

    stale = tmp_path / "stale_baseline.txt"
    stale.write_text(
        "GL101 pkg/gone.py:nope:.item :: matches nothing anymore\n"
    )
    assert main([str(root), "--no-baseline", "--select", "host-sync"]) == 1
    assert main([str(root), "--baseline", str(stale)]) == 1  # stale entry

    bad = tmp_path / "bad_baseline.txt"
    bad.write_text("GL101 missing-justification\n")
    assert main([str(root), "--baseline", str(bad)]) == 2  # parse error


def test_cli_select_scopes_baseline(tmp_path):
    """A pass-filtered run must neither report other passes' baseline
    entries as stale nor (with --update-baseline) delete them."""
    root = tmp_path / "pkg"
    root.mkdir()
    (root / "__init__.py").write_text("")
    (root / "bad.py").write_text(textwrap.dedent(_VIOLATION_PKG["bad.py"]))
    findings, _ = run_analysis(str(root), passes=["host-sync"])
    bl = tmp_path / "bl.txt"
    bl.write_text(
        f"{findings[0].key} :: fixture: intentional\n"
        "GL501 pkg/other.py:-:oldkey :: covered by a pass not selected here\n"
    )
    # the GL501 entry is out of scope for a host-sync-only run: not stale
    assert main([str(root), "--select", "host-sync", "--baseline", str(bl)]) == 0
    # ...and a filtered --update-baseline keeps it (and the justification)
    assert main(
        [str(root), "--select", "host-sync", "--baseline", str(bl),
         "--update-baseline"]
    ) == 0
    kept = Baseline.load(str(bl))
    assert set(kept.entries) == {
        findings[0].key,
        "GL501 pkg/other.py:-:oldkey",
    }
    assert kept.entries[findings[0].key].justification == "fixture: intentional"


def test_cli_select_on_real_tree_exits_zero():
    """The committed GL201 entries belong to recompile-hazard: selecting a
    different pass must not see them as stale."""
    assert main([TREE, "--select", "host-sync", "--baseline", BASELINE]) == 0


def test_cli_format_json_and_sarif(tmp_path, capsys):
    root = tmp_path / "pkg"
    root.mkdir()
    (root / "__init__.py").write_text("")
    (root / "bad.py").write_text(textwrap.dedent(_VIOLATION_PKG["bad.py"]))

    import json

    assert main([str(root), "--no-baseline", "--format", "json"]) == 1
    doc = json.loads(capsys.readouterr().out)
    assert len(doc["findings"]) == 1
    assert doc["findings"][0]["code"] == "GL101"
    assert doc["baselined"] == 0 and doc["stale_baseline_entries"] == []

    # sarif to stdout: a valid 2.1.0 doc with one result per finding
    assert main([str(root), "--no-baseline", "--format", "sarif"]) == 1
    doc = json.loads(capsys.readouterr().out)
    assert doc["version"] == "2.1.0"
    results = doc["runs"][0]["results"]
    assert [r["ruleId"] for r in results] == ["GL101"]
    loc = results[0]["locations"][0]["physicalLocation"]
    assert loc["artifactLocation"]["uri"] == "pkg/bad.py"
    assert loc["region"]["startLine"] > 0
    rules = {r["id"] for r in doc["runs"][0]["tool"]["driver"]["rules"]}
    assert rules == {"GL101"}

    # --output: the doc lands in the file, human rendering stays on stdout
    out_path = tmp_path / "lint.sarif"
    assert main(
        [str(root), "--no-baseline", "--format", "sarif", "--output",
         str(out_path)]
    ) == 1
    human = capsys.readouterr().out
    assert "GL101" in human and "graftlint:" in human
    doc = json.loads(out_path.read_text())
    assert doc["runs"][0]["results"]

    # --output without a structured format is a usage error
    assert main([str(root), "--output", str(out_path)]) == 2


def test_cli_multi_root_single_run(tmp_path, capsys):
    """Two roots share one run and one baseline: a clean root does not mark
    the other root's baseline entries stale."""
    a = tmp_path / "pkg_a"
    b = tmp_path / "pkg_b"
    for root in (a, b):
        root.mkdir()
        (root / "__init__.py").write_text("")
    (a / "bad.py").write_text(textwrap.dedent(_VIOLATION_PKG["bad.py"]))

    findings, ctxs = run_analysis([str(a), str(b)], passes=["host-sync"])
    assert len(ctxs) == 2 and len(findings) == 1
    bl = tmp_path / "bl.txt"
    bl.write_text(f"{findings[0].key} :: fixture: intentional\n")
    assert main([str(a), str(b), "--baseline", str(bl)]) == 0
    out = capsys.readouterr().out
    assert "stale" not in out


def test_cli_rejects_no_baseline_with_update_baseline(tmp_path):
    # the combination would rewrite the baseline without loading it,
    # destroying every committed justification
    root = tmp_path / "pkg"
    root.mkdir()
    (root / "__init__.py").write_text("")
    marker = tmp_path / "GRAFTLINT_BASELINE.txt"
    marker.write_text("# untouched\n")
    assert main([str(root), "--no-baseline", "--update-baseline"]) == 2
    assert marker.read_text() == "# untouched\n"


def test_analysis_imports_without_jax():
    """Lint-only CI contract: importing trlx_tpu.analysis AND loading every
    registered pass (ownership/determinism included — all_passes() imports
    the pass modules) must not pull in the training stack — the package
    root's `train` is a lazy attribute, and no pass module may import jax
    at module scope."""
    proc = subprocess.run(
        [
            sys.executable,
            "-c",
            "import sys; from trlx_tpu.analysis import all_passes; "
            "names = set(all_passes()); "
            "assert {'ownership', 'determinism', 'kernel-discipline'} "
            "<= names, names; "
            "assert 'jax' not in sys.modules, 'loading the passes pulled in jax'",
        ],
        capture_output=True,
        text=True,
        cwd=REPO_ROOT,
    )
    assert proc.returncode == 0, proc.stderr


def test_cli_syntax_errors_fail_honestly(tmp_path, capsys):
    root = tmp_path / "pkg"
    root.mkdir()
    (root / "__init__.py").write_text("")
    (root / "broken.py").write_text("def f(:\n")
    assert main([str(root), "--no-baseline"]) == 1
    out = capsys.readouterr().out
    assert "FAILED" in out and "unparseable" in out
    assert "graftlint: OK" not in out
    # --update-baseline must refuse: the broken file's findings are unknown
    assert main([str(root), "--no-baseline", "--update-baseline"]) == 2


def test_default_baseline_is_scan_root_adjacent_not_cwd(tmp_path, monkeypatch):
    """Linting a scratch package from the repo root must not pick up (or
    ever rewrite) the repo's committed GRAFTLINT_BASELINE.txt."""
    from trlx_tpu.analysis.core import _default_baseline

    monkeypatch.chdir(REPO_ROOT)
    root = tmp_path / "pkg"
    root.mkdir()
    (root / "__init__.py").write_text("")
    assert _default_baseline(str(root)) is None
    assert _default_baseline(TREE) == BASELINE
    # clean scratch package from the repo root: no spurious stale entries
    assert main([str(root)]) == 0


# ---------------------------------------------------------------------------
# the tier-1 self-run: the real tree, the committed baseline
# ---------------------------------------------------------------------------


_SELF_RUN = {}  # wall-clock seconds of the fixture's full multi-root run


@pytest.fixture(scope="module")
def tree_findings():
    # the CI gate's exact scan surface: the package AND scripts/ (bench/
    # evidence tooling spawns processes and writes spool files — linted
    # with the same baseline, in the same run)
    import time as _time

    t0 = _time.perf_counter()
    findings, ctxs = run_analysis([TREE, SCRIPTS])
    _SELF_RUN["seconds"] = _time.perf_counter() - t0
    for ctx in ctxs:
        assert ctx.errors == [], f"unparseable sources: {ctx.errors}"
    return findings


def test_self_run_tree_is_clean(tree_findings):
    """THE gate: every finding on the committed tree is baselined (with a
    justification) and every baseline entry still fires."""
    baseline = Baseline.load(BASELINE)
    new, stale = baseline.apply(tree_findings)
    assert new == [], "non-baselined findings:\n" + "\n".join(
        f.render() for f in new
    )
    assert stale == [], "stale baseline entries (fix shipped? delete them):\n" + \
        "\n".join(e.key for e in stale)
    for entry in baseline.entries.values():
        assert not entry.needs_justification, entry.key


def test_self_run_every_baseline_entry_is_load_bearing(tree_findings):
    """Removing ANY single baseline entry must fail the gate."""
    baseline = Baseline.load(BASELINE)
    assert baseline.entries, "baseline unexpectedly empty"
    for key in list(baseline.entries):
        pruned = Baseline(
            {k: v for k, v in baseline.entries.items() if k != key}
        )
        new, _stale = pruned.apply(tree_findings)
        assert [f.key for f in new] and all(f.key == key for f in new), key


def test_self_run_detects_injected_violation(tree_findings, tmp_path):
    """A fresh violation (not in the baseline) must fail the gate — the
    committed baseline cannot mask new regressions."""
    findings = lint_pkg(tmp_path, _VIOLATION_PKG, passes=["host-sync"])
    baseline = Baseline.load(BASELINE)
    new, _ = baseline.apply(list(tree_findings) + findings)
    assert [f.key for f in new] == [findings[0].key]


def test_self_run_runtime_budget(tree_findings):
    """The full multi-root self-run (ALL passes, both scan roots) stays
    under a fixed wall-clock budget: every added pass re-walks the tree, so
    an accidentally quadratic analysis would quietly turn the tier-1 gate
    into the slowest test in the suite. ~11s today; the budget leaves slow-
    CI headroom while catching an order-of-magnitude regression."""
    assert "seconds" in _SELF_RUN, "fixture did not record its runtime"
    assert _SELF_RUN["seconds"] < 90.0, (
        f"graftlint self-run took {_SELF_RUN['seconds']:.1f}s (budget 90s) — "
        "profile the newest pass; reachability and registry scans must stay "
        "near-linear in module count"
    )


def test_self_run_detects_injected_ownership_and_determinism_violations(
    tree_findings, tmp_path
):
    """The acceptance shapes for the GL80x/GL90x families: a leaked block
    ref on an exception path, a double release, an unsorted spool scan, and
    a wall-clock read feeding store content each surface EXACTLY their
    finding through the committed baseline."""
    leak = lint_pkg(tmp_path, _LEAK_PKG, passes=["ownership"])
    dbl = lint_pkg(tmp_path, _DOUBLE_RELEASE_PKG, passes=["ownership"], name="pkg_dbl")
    scan = lint_pkg(tmp_path, _UNSORTED_SCAN_PKG, passes=["determinism"], name="pkg_scan")
    stamp = lint_pkg(tmp_path, _TIME_STORE_PKG, passes=["determinism"], name="pkg_time")
    assert codes(leak) == ["GL801"]
    assert codes(dbl) == ["GL802"]
    assert codes(scan) == ["GL903"]
    assert codes(stamp) == ["GL901"]
    baseline = Baseline.load(BASELINE)
    new, _ = baseline.apply(list(tree_findings) + leak + dbl + scan + stamp)
    assert sorted(f.code for f in new) == ["GL801", "GL802", "GL901", "GL903"]


def test_sarif_fingerprints_are_line_drift_stable(tmp_path):
    """CI inline annotations key on partialFingerprints: every SARIF result
    (finding, stale entry, parse error) carries a graftlintKey/v1 derived
    from the line-number-free finding key, so an edit ABOVE a finding moves
    region.startLine but never the fingerprint."""
    import json

    root = tmp_path / "pkg"
    root.mkdir()
    (root / "__init__.py").write_text("")
    (root / "bad.py").write_text(textwrap.dedent(_VIOLATION_PKG["bad.py"]))

    def sarif_results():
        out = tmp_path / "out.sarif"
        main([str(root), "--no-baseline", "--format", "sarif", "--output", str(out)])
        return json.loads(out.read_text())["runs"][0]["results"]

    first = sarif_results()
    assert len(first) == 1
    fp = first[0]["partialFingerprints"]["graftlintKey/v1"]
    line = first[0]["locations"][0]["physicalLocation"]["region"]["startLine"]
    # the fingerprint IS the baseline key: line-free by construction
    findings, _ = run_analysis(str(root), passes=["host-sync"])
    assert fp == findings[0].key

    # drift: push the finding down; startLine moves, the fingerprint doesn't
    (root / "bad.py").write_text(
        "# pad\n# pad\n# pad\n" + textwrap.dedent(_VIOLATION_PKG["bad.py"])
    )
    second = sarif_results()
    assert second[0]["partialFingerprints"]["graftlintKey/v1"] == fp
    assert second[0]["locations"][0]["physicalLocation"]["region"]["startLine"] != line

    # stale-entry and parse-error results carry fingerprints too
    bl = tmp_path / "bl.txt"
    bl.write_text(
        f"{fp} :: fixture\nGL101 pkg/gone.py:f:.item :: matches nothing\n"
    )
    (root / "broken.py").write_text("def f(:\n")
    out = tmp_path / "out2.sarif"
    main([str(root), "--baseline", str(bl), "--format", "sarif", "--output", str(out)])
    results = json.loads(out.read_text())["runs"][0]["results"]
    fps = {r["partialFingerprints"]["graftlintKey/v1"] for r in results}
    assert "GL000 stale:GL101 pkg/gone.py:f:.item" in fps
    assert "GL000 parse:pkg/broken.py" in fps
    assert all("partialFingerprints" in r for r in results)


def test_self_run_detects_injected_concurrency_violations(tree_findings, tmp_path):
    """The acceptance shapes: an unguarded cross-thread write and a
    process_index()-guarded allgather each surface under their own code
    through the committed baseline."""
    escape = lint_pkg(tmp_path, _ESCAPE_PKG, passes=["thread-escape"])
    guarded = lint_pkg(
        tmp_path,
        {
            "rank.py": """
            import jax
            import numpy as np
            from jax.experimental import multihost_utils

            def exchange(flag):
                if jax.process_index() == 0:
                    return multihost_utils.process_allgather(np.asarray(flag))
                return None
            """
        },
        passes=["collective-discipline"],
        name="pkg_rank",
    )
    assert codes(escape) == ["GL403"] and codes(guarded) == ["GL701"]
    baseline = Baseline.load(BASELINE)
    new, _ = baseline.apply(list(tree_findings) + escape + guarded)
    assert sorted(f.code for f in new) == ["GL403", "GL701"]


def test_self_run_detects_injected_kernel_violations(tree_findings, tmp_path):
    """The acceptance shapes for the GL10xx family: an ungated
    pallas_call entry, a literal-stamped *_pallas gauge, an
    ndarray-closure kernel body, and an unregistered kernel flavor each
    surface EXACTLY their finding through the committed baseline."""
    _touch_parity_test(tmp_path)
    ungated = lint_pkg(
        tmp_path,
        {
            "pallas_utils.py": _PALLAS_UTILS_FIXTURE,
            "kern.py": """
            from jax.experimental import pallas as pl
            from pkg_gate.pallas_utils import has_pallas_tpu

            def _kernel(x_ref, o_ref):
                o_ref[...] = x_ref[...]

            def attention_reference(x):
                return x

            def flash_attention(x):
                return pl.pallas_call(_kernel, out_shape=x)(x)

            def flash_attention_bwd_chunk(x):
                if not has_pallas_tpu():
                    return attention_reference(x)
                return pl.pallas_call(_kernel, out_shape=x)(x)
            """,
        },
        passes=["kernel-discipline"],
        name="pkg_gate",
    )
    stamp = lint_pkg(
        tmp_path,
        {"stamp.py": 'def f(g):\n    g["decode_pallas"] = 1.0\n'},
        passes=["kernel-discipline"],
        name="pkg_stamp",
    )
    impure = lint_pkg(
        tmp_path,
        {
            "pallas_utils.py": _PALLAS_UTILS_FIXTURE,
            "kern.py": """
            import numpy as np
            from jax.experimental import pallas as pl
            from pkg_pure.pallas_utils import has_pallas_tpu

            TABLE = np.arange(8)

            def _kernel(x_ref, o_ref):
                o_ref[...] = x_ref[...] * TABLE

            def attention_reference(x):
                return x

            def flash_attention(x):
                if not has_pallas_tpu():
                    return attention_reference(x)
                return pl.pallas_call(_kernel, out_shape=x)(x)

            def flash_attention_bwd_chunk(x):
                return flash_attention(x)
            """,
        },
        passes=["kernel-discipline"],
        name="pkg_pure",
    )
    unregistered = lint_pkg(
        tmp_path,
        {
            "pallas_utils.py": _PALLAS_UTILS_FIXTURE,
            "kern.py": """
            from jax.experimental import pallas as pl
            from pkg_reg.pallas_utils import has_pallas_tpu

            def _kernel(x_ref, o_ref):
                o_ref[...] = x_ref[...]

            def mystery_kernel(x):
                if not has_pallas_tpu():
                    return x
                return pl.pallas_call(_kernel, out_shape=x)(x)
            """,
        },
        passes=["kernel-discipline"],
        name="pkg_reg",
    )
    assert codes(ungated) == ["GL1001"]
    assert codes(stamp) == ["GL1002"]
    assert codes(impure) == ["GL1003"]
    assert codes(unregistered) == ["GL1004"]
    baseline = Baseline.load(BASELINE)
    new, _ = baseline.apply(
        list(tree_findings) + ungated + stamp + impure + unregistered
    )
    assert sorted(f.code for f in new) == [
        "GL1001", "GL1002", "GL1003", "GL1004",
    ]


def test_sarif_fingerprints_on_kernel_findings(tmp_path):
    """GL10xx results carry the same line-drift-stable graftlintKey/v1
    partialFingerprints as every other pass: padding lines above a
    literal-stamped gauge moves region.startLine, never the key."""
    import json

    root = tmp_path / "pkg"
    root.mkdir()
    (root / "__init__.py").write_text("")
    src = 'def f(g):\n    g["decode_pallas"] = 1.0\n'
    (root / "stamp.py").write_text(src)

    def sarif_results():
        out = tmp_path / "out.sarif"
        main([
            str(root), "--no-baseline", "--select", "kernel-discipline",
            "--format", "sarif", "--output", str(out),
        ])
        return json.loads(out.read_text())["runs"][0]["results"]

    first = sarif_results()
    assert [r["ruleId"] for r in first] == ["GL1002"]
    fp = first[0]["partialFingerprints"]["graftlintKey/v1"]
    line = first[0]["locations"][0]["physicalLocation"]["region"]["startLine"]
    findings, _ = run_analysis(str(root), passes=["kernel-discipline"])
    assert fp == findings[0].key
    assert fp == "GL1002 pkg/stamp.py:f:decode_pallas"

    (root / "stamp.py").write_text("# pad\n# pad\n" + src)
    second = sarif_results()
    assert second[0]["partialFingerprints"]["graftlintKey/v1"] == fp
    assert (
        second[0]["locations"][0]["physicalLocation"]["region"]["startLine"]
        != line
    )


def test_lint_py_ci_entry():
    """scripts/lint.py (the CI entry point) exits 0 on the committed tree."""
    proc = subprocess.run(
        [sys.executable, os.path.join(REPO_ROOT, "scripts", "lint.py")],
        capture_output=True,
        text=True,
        cwd=REPO_ROOT,
        env={**os.environ, "JAX_PLATFORMS": "cpu"},
        timeout=300,
    )
    assert proc.returncode == 0, proc.stdout + proc.stderr
    assert "graftlint: OK" in proc.stdout


def test_lint_py_sarif_entry(tmp_path):
    """`scripts/lint.py --sarif PATH` — the exact invocation lint.yml and
    `make lint-sarif` run — exits 0 on the committed tree and writes a
    well-formed SARIF doc with zero non-baselined results (all passes,
    GL10xx included, run in this entry point: scripts/lint.py selects
    nothing, so all_passes() is the active set)."""
    import json

    out = tmp_path / "graftlint.sarif"
    proc = subprocess.run(
        [
            sys.executable,
            os.path.join(REPO_ROOT, "scripts", "lint.py"),
            "--sarif",
            str(out),
        ],
        capture_output=True,
        text=True,
        cwd=REPO_ROOT,
        env={**os.environ, "JAX_PLATFORMS": "cpu"},
        timeout=300,
    )
    assert proc.returncode == 0, proc.stdout + proc.stderr
    run = json.loads(out.read_text())["runs"][0]
    assert run["results"] == []  # clean tree: nothing to annotate


def test_pass_registry_and_codes():
    passes = all_passes()
    assert set(passes) == {
        "host-sync", "recompile-hazard", "donation-safety",
        "lock-discipline", "thread-escape", "collective-discipline",
        "ownership", "determinism", "kernel-discipline",
        "metric-names", "span-names", "config-keys",
    }
    seen = set()
    for cls in passes.values():
        assert cls.codes, cls.name
        overlap = seen & set(cls.codes)
        assert not overlap, f"duplicate finding codes: {overlap}"
        seen |= set(cls.codes)


def test_tree_jit_surface_is_covered(tree_findings):
    """Guard against the call graph going vacuous: the real tree must keep
    rooting the known jit surface (train step, samplers, slot refill) and
    tracing through it."""
    ctx = AnalysisContext(TREE)
    g = ctx.callgraph
    root_names = {r.fn.qualname for r in g.jit_roots}
    assert any("_build_train_step.<locals>.train_step" in n for n in root_names)
    assert any("_get_score_fn" in n for n in root_names)
    assert any("decode_segment" in n for n in root_names)
    traced_mods = {f.module.modname for f in g.traced_functions()}
    assert "trlx_tpu.ops.sampling" in traced_mods
    assert "trlx_tpu.ops.slot_refill" in traced_mods
    assert "trlx_tpu.ops.speculative" in traced_mods
    assert len(g.traced) >= 60
