"""The program store (``trlx_tpu/utils/programs.py``, PR 50): a warm start
loads the job's own compiled programs without tracing them.

Every test has a store of its own under ``tmp_path`` (``conftest.py``), so what
is asserted about hits and misses never depends on what another test, worker
or run left on disk. The tests that need a warm store make it themselves: a
second ``StoredProgram`` under the same key, or a second process.
"""

import copy
import json
import os
import pickle
import subprocess
import sys
import textwrap
import types

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from trlx_tpu.data.default_configs import (
    default_grpo_config,
    default_ilql_config,
    default_ppo_config,
    default_sft_config,
)
from trlx_tpu.observability import tracing
from trlx_tpu.observability.watchdogs import RecompileWatchdog
from trlx_tpu.trainer.base import TrainState
from trlx_tpu.utils import programs
from trlx_tpu.utils.programs import ProgramStore, stored_program

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


@pytest.fixture(autouse=True)
def _true_compiles():
    """XLA:CPU cannot serialize an executable that JAX's persistent compile
    cache gave it, and the store then keeps nothing (``_compile_and_write``).
    These tests want entries written: they compile in earnest, whatever an
    earlier run of the suite left in the shared compile cache."""
    from jax.experimental.compilation_cache import compilation_cache

    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield
    jax.config.update("jax_enable_compilation_cache", True)
    compilation_cache.reset_cache()


def _counts():
    m = tracing.mark()
    return {k: m.get(f"runtime/store_{k}", 0.0) for k in ("hits", "misses")}


def _since(before):
    now = _counts()
    return {k: now[k] - before[k] for k in now}


def _entries():
    root = programs.store_dir()
    return sorted(os.listdir(root)) if os.path.isdir(root) else []


# ---------------------------------------------------------------------------
# the wrapper against jax.jit
# ---------------------------------------------------------------------------


def train_step(state, batch, loss_scale):
    """A donating step with a ``flax.struct`` node in and out, as
    ``trainer/base.py::_build_train_step`` makes them."""
    rng, sub = jax.random.split(state.rng)
    noise = jax.random.normal(sub, batch["x"].shape)
    grads = jax.tree_util.tree_map(
        lambda p: p * jnp.mean((batch["x"] + noise) * loss_scale), state.params)
    params = jax.tree_util.tree_map(lambda p, g: (p - 0.1 * g).astype(p.dtype), state.params, grads)
    moments = jax.tree_util.tree_map(
        lambda m, g: (0.9 * m + g).astype(m.dtype), state.opt_state, grads)
    new = TrainState(params=params, opt_state=moments, step=state.step + 1, rng=rng)
    return new, {"loss": jnp.sum(batch["x"]) * loss_scale, "rows": batch["n"].sum()}


def _state():
    params = {"w": jnp.arange(12.0).reshape(3, 4), "b": {"bias": jnp.ones((4,), jnp.bfloat16)}}
    return TrainState(params=params, opt_state=jax.tree_util.tree_map(jnp.zeros_like, params),
                      step=jnp.zeros((), jnp.int32), rng=jax.random.PRNGKey(3))


def _batch():
    return {"x": np.linspace(0.0, 1.0, 8, dtype=np.float32), "n": np.arange(8, dtype=np.int32)}


@pytest.mark.parametrize("warm", [False, True], ids=["compiled", "loaded"])
def test_donating_train_step_is_bit_equal_to_jit(warm):
    key = ["a donating step"]
    if warm:  # an earlier program under the same key leaves the entry behind
        stored_program("train_step", train_step, key, donate_argnums=(0,))(
            _state(), _batch(), np.float32(1.0))
    before = _counts()
    program = stored_program("train_step", train_step, key, donate_argnums=(0,))
    plain = jax.jit(train_step, donate_argnums=(0,))
    mine, theirs = _state(), _state()
    for scale in (1.0, 0.5, float("nan")):
        donated = jax.tree_util.tree_leaves(mine)
        mine, stats = program(mine, _batch(), np.float32(scale))
        theirs, want = plain(theirs, _batch(), np.float32(scale))
        assert all(x.is_deleted() for x in donated)
        assert isinstance(mine, TrainState)
        for got, ref in zip(jax.tree_util.tree_leaves((mine, stats)),
                            jax.tree_util.tree_leaves((theirs, want))):
            assert got.dtype == ref.dtype and got.shape == ref.shape
            assert np.asarray(got).tobytes() == np.asarray(ref).tobytes()  # NaNs and all
    # one signature, three calls: the step's own output is the next call's input
    assert program._cache_size() == 1
    assert _since(before) == ({"hits": 1, "misses": 0} if warm else {"hits": 0, "misses": 1})
    assert len(_entries()) == 1


def test_keywords_and_none_leaves_are_part_of_the_signature():
    def score(rows, take=None):
        return rows * 2 if take is None else rows[take]

    program = stored_program("score", score, ["kw"])
    rows = jnp.arange(6.0)
    np.testing.assert_array_equal(program(rows), rows * 2)
    np.testing.assert_array_equal(program(rows, take=None), rows * 2)
    np.testing.assert_array_equal(program(rows, take=np.array([4, 1])), [4.0, 1.0])
    assert program._cache_size() == 3  # positional only, take=None, take an array


def test_tracers_and_a_disabled_jit_go_to_the_plain_jit():
    program = stored_program("square", lambda x: jnp.sum(x * x), ["plain"])
    x = jnp.arange(3.0)
    np.testing.assert_array_equal(jax.grad(program)(x), 2 * x)
    assert jax.eval_shape(program, x).shape == ()
    with jax.disable_jit():
        assert float(program(x)) == 5.0
    assert _entries() == [] and _counts()["misses"] == tracing.mark().get("runtime/store_misses", 0.0)
    assert float(program(x)) == 5.0 and len(_entries()) == 1
    # .lower is the inner jit's: the MFU gauge and memory analyses use it
    assert "stablehlo" in program.lower(x).as_text()


def test_lazy_jit_kwargs_run_on_a_miss_only():
    asked = []

    def jit_kwargs():
        asked.append(1)
        return {"donate_argnums": (0,)}

    fn = lambda x: x + 1  # noqa: E731
    stored_program("inc", fn, ["lazy"], jit_kwargs)(jnp.zeros(3))
    assert asked == [1]
    x = jnp.zeros(3)
    out = stored_program("inc", fn, ["lazy"], jit_kwargs)(x)
    assert asked == [1] and x.is_deleted()  # the loaded executable donates as it was compiled to
    np.testing.assert_array_equal(out, np.ones(3))


def test_no_directory_no_store(monkeypatch, tmp_path):
    monkeypatch.setattr(programs, "store_dir", lambda: None)
    before = _counts()
    program = stored_program("inc", lambda x: x + 1, ["nowhere"])
    np.testing.assert_array_equal(program(jnp.zeros(2)), np.ones(2))
    assert _since(before) == {"hits": 0, "misses": 0} and program._cache_size() == 1
    assert not os.path.exists(tmp_path / "programs")


# ---------------------------------------------------------------------------
# entries on disk
# ---------------------------------------------------------------------------


def _damage(kind, path):
    if kind == "truncated":
        with open(path, "rb") as f:
            data = f.read()
        with open(path, "wb") as f:
            f.write(data[: len(data) // 2])
    elif kind == "garbage":
        with open(path, "wb") as f:
            f.write(os.urandom(4096))
    elif kind == "empty":
        open(path, "wb").close()
    else:  # a whole entry of another key under this name
        with open(path, "rb") as f:
            entry = pickle.load(f)
        with open(path, "wb") as f:
            pickle.dump(dict(entry, key="0" * 64), f)


@pytest.mark.parametrize("kind", ["truncated", "garbage", "empty", "another_key"])
def test_an_unreadable_entry_is_a_miss_that_is_rewritten(kind, trlx_log_records):
    fn = lambda x: x * 3  # noqa: E731
    stored_program("triple", fn, ["damage"])(jnp.ones(4))
    (name,) = _entries()
    path = os.path.join(programs.store_dir(), name)
    _damage(kind, path)
    before = _counts()
    np.testing.assert_array_equal(stored_program("triple", fn, ["damage"])(jnp.ones(4)), 3 * np.ones(4))
    assert _since(before) == {"hits": 0, "misses": 1}
    assert any("unreadable" in r.getMessage() for r in trlx_log_records)
    assert _entries() == [name]  # no temporary left beside it
    before = _counts()
    np.testing.assert_array_equal(stored_program("triple", fn, ["damage"])(jnp.ones(4)), 3 * np.ones(4))
    assert _since(before) == {"hits": 1, "misses": 0}


WRITER = """
import sys
import jax.numpy as jnp
from trlx_tpu.utils import programs
programs.store_dir = lambda: sys.argv[1]
out = programs.stored_program("poly", lambda x: x * x + 1, ["two writers"])(jnp.arange(4.0))
print(out.tolist())
"""


def test_two_processes_writing_one_entry_leave_a_loadable_file(tmp_path):
    root = programs.store_dir()
    env = dict(os.environ, PYTHONPATH=REPO, JAX_PLATFORMS="cpu")
    writers = [subprocess.Popen([sys.executable, "-c", WRITER, root], env=env,
                                stdout=subprocess.PIPE, stderr=subprocess.PIPE, text=True)
               for _ in range(2)]
    for w in writers:
        out, err = w.communicate(timeout=180)
        assert w.returncode == 0, err[-2000:]
        assert json.loads(out.strip().splitlines()[-1]) == [1.0, 2.0, 5.0, 10.0]
    (name,) = _entries()  # one entry, and neither writer's temporary
    before = _counts()
    out = stored_program("poly", lambda x: x * x + 1, ["two writers"])(jnp.arange(4.0))
    assert out.tolist() == [1.0, 2.0, 5.0, 10.0]
    assert _since(before) == {"hits": 1, "misses": 0}


def test_cache_size_and_the_watchdog_count_a_loaded_signature_like_a_compiled_one():
    fn = lambda x: x.sum(axis=-1)  # noqa: E731
    rungs = [(2, 16), (2, 32), (2, 64)]

    def run(program, shapes, planned):
        dog = RecompileWatchdog()
        for shape in shapes:
            program(jnp.ones(shape))
            dog.observe("train_step", program, planned=shape if shape in planned else None)
        return dog.excess_compiles("train_step")

    cold = stored_program("ladder", fn, ["ladder"])
    assert run(cold, rungs + rungs, rungs) == 0 and cold._cache_size() == 3
    warm = stored_program("ladder", fn, ["ladder"])
    before = _counts()
    assert run(warm, rungs + rungs, rungs) == 0 and warm._cache_size() == 3
    assert _since(before) == {"hits": 3, "misses": 0}
    # a shape nobody planned is drift whether its executable is loaded or compiled
    for key in ("ladder", "another ladder"):  # the first rung loaded, or compiled
        assert run(stored_program("ladder", fn, [key]), rungs[:1] + [(2, 24)], rungs) == 1


# ---------------------------------------------------------------------------
# the guard
# ---------------------------------------------------------------------------


def test_verify_passes_on_a_fresh_store_and_fails_on_a_planted_digest():
    def build():
        store = ProgramStore()
        half = store.program("half", lambda x: x / 2, "site")
        twice = store.program("twice", lambda x, n: x * n, "site")
        half(jnp.ones(3)), twice(jnp.ones(3), 2), twice(np.ones(5, np.float32), 2)
        return types.SimpleNamespace(programs=store)

    cold = build()
    assert programs.verify(cold) == 0  # it compiled what it runs: nothing to compare
    assert programs.verify(build()) == 3
    # the program changes where nothing the key reads does: a stale entry
    path = os.path.join(programs.store_dir(), [n for n in _entries() if n.startswith("half")][0])
    with open(path, "rb") as f:
        entry = pickle.load(f)
    with open(path, "wb") as f:
        pickle.dump(dict(entry, stablehlo_sha256="f" * 64), f)
    with pytest.raises(RuntimeError, match="half.*was compiled from StableHLO ffff"):
        programs.verify(build())


# ---------------------------------------------------------------------------
# the key
# ---------------------------------------------------------------------------

CONFIGS = {"ppo": default_ppo_config, "grpo": default_grpo_config,
           "ilql": default_ilql_config, "sft": default_sft_config}
SECTIONS = ("method", "model", "optimizer", "scheduler", "tokenizer", "train", "parallel",
            "resilience", "engine", "async_rl", "serve")


class _Dict:
    def __init__(self, d):
        self.d = d

    def to_dict(self):
        return self.d


def _other(value):
    if isinstance(value, bool):
        return not value
    if isinstance(value, (int, float)):
        return value + 1
    if isinstance(value, str):
        return value + "x"
    if isinstance(value, (list, tuple)):
        return list(value) + [1]
    if isinstance(value, dict):
        return dict(value, another=1)
    assert value is None, value
    return "x"


def _leaves(tree, prefix=()):
    for k, v in tree.items():
        if isinstance(v, dict) and v:
            yield from _leaves(v, prefix + (k,))
        else:
            yield prefix + (k,)


@pytest.mark.parametrize("section", SECTIONS)
@pytest.mark.parametrize("method", sorted(CONFIGS))
def test_every_config_field_not_excluded_changes_the_key(method, section):
    base = CONFIGS[method]().to_dict()
    fields = programs.config_fields(_Dict(base))
    parts = ProgramStore(_Dict(base))._parts
    seen = 0
    for path in _leaves(base[section], (section,)):
        dotted = ".".join(path)
        changed = copy.deepcopy(base)
        node = changed
        for k in path[:-1]:
            node = node[k]
        node[path[-1]] = _other(node[path[-1]])
        if dotted in programs.EXCLUDED_CONFIG_FIELDS:
            assert programs.config_fields(_Dict(changed)) == fields, dotted
            assert ProgramStore(_Dict(changed))._parts == parts, dotted
            continue
        assert programs.config_fields(_Dict(changed)) != fields, dotted
        assert ProgramStore(_Dict(changed))._parts != parts, dotted
        seen += 1
    assert seen >= 2
    assert all(f.split(".")[0] == "train" for f in programs.EXCLUDED_CONFIG_FIELDS)
    # what the learning-rate schedule inside the train step may read stays in
    assert {"train.total_steps", "train.epochs"} <= set(fields)


def _toy(**train):
    return default_ppo_config().evolve(
        train=dict(dict(seq_length=24, batch_size=8, total_steps=2, epochs=1, tracker=None,
                        eval_interval=1000, checkpoint_interval=1000), **train),
        model=dict(model_path="builtin:gpt2-test", num_layers_unfrozen=1),
        tokenizer=dict(tokenizer_path="builtin:bytes"),
        method=dict(num_rollouts=8, chunk_size=8, ppo_epochs=1,
                    gen_kwargs=dict(max_new_tokens=8, top_k=0, top_p=1.0, do_sample=True)),
    )


def _digests(config):
    """``entry file -> StableHLO digest`` of every program one toy PPO job runs
    (a cold store: each is traced, lowered and its text digested)."""
    import trlx_tpu

    trainer = trlx_tpu.train(
        reward_fn=lambda samples, **kw: [float(len(s)) for s in samples],
        prompts=["hello world"] * 8, eval_prompts=["hello"] * 8, config=config)
    out = {}
    for program in trainer.programs._programs:
        for held in program._held.values():
            assert not held.loaded
            out[os.path.basename(held.path)] = held.digest
    return out


def test_an_executable_the_compile_cache_gave_is_not_kept_on_the_cpu(tmp_path, monkeypatch):
    """XLA:CPU serializes an executable it deserialized without its object
    code (the entry would load and fail at its first call), so a program whose
    compile was a hit of JAX's persistent cache stays out of the store here:
    by the executable's own devices, whatever ``jax.default_backend`` is
    patched to say (``tests/test_aot_tpu.py`` and the rehearsals say "tpu")."""
    from jax.experimental.compilation_cache import compilation_cache

    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")

    unique = float(int.from_bytes(os.urandom(3), "little"))  # a module no earlier run compiled
    fn = lambda x: jnp.cumsum(x * 7) + unique  # noqa: E731
    jax.config.update("jax_enable_compilation_cache", True)
    compilation_cache.reset_cache()
    floor = jax.config.jax_persistent_cache_min_compile_time_secs
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    try:
        stored_program("cum", fn, ["from the cache"])(jnp.ones(4))  # compiled here: kept
        (name,) = _entries()
        os.unlink(os.path.join(programs.store_dir(), name))
        jax.clear_caches()  # a new process: nothing compiled is held in memory
        again = stored_program("cum", fn, ["from the cache"])
        again(jnp.ones(4))  # the compile cache gives the executable: not kept
        assert _entries() == [] and again._cache_size() == 1
    finally:
        jax.config.update("jax_persistent_cache_min_compile_time_secs", floor)


@pytest.fixture(scope="module")
def baseline_digests(tmp_path_factory):
    root = str(tmp_path_factory.mktemp("baseline"))
    keep, programs.store_dir = programs.store_dir, lambda: os.path.join(root, "programs")
    try:
        return _digests(_toy(checkpoint_dir=os.path.join(root, "ckpts")))
    finally:
        programs.store_dir = keep


EXCLUDED_VALUES = {
    "train.seed": 4242,
    "train.checkpoint_dir": None,  # another directory, below
    "train.logging_dir": None,
    "train.rollout_logging_dir": None,
    "train.tracker": "jsonl",
    "train.project_name": "another-project",
    "train.entity_name": "somebody",
    "train.group_name": "a-group",
    "train.tags": ["a", "b"],
}


def test_the_excluded_list_is_the_one_proven_here():
    assert set(EXCLUDED_VALUES) == set(programs.EXCLUDED_CONFIG_FIELDS)


@pytest.mark.parametrize("field", sorted(EXCLUDED_VALUES))
def test_an_excluded_field_leaves_every_program_byte_identical(field, baseline_digests, tmp_path):
    """The proof that the list is safe: under another value of the field the
    job runs the same programs (same entry names: the same key) and each lowers
    to the same StableHLO text."""
    name = field.split(".")[1]
    value = EXCLUDED_VALUES[field]
    train = {"checkpoint_dir": str(tmp_path / "ckpts")}
    train[name] = str(tmp_path / name) if value is None else value
    got = _digests(_toy(**train))
    assert len(got) >= 9  # make_params, init_state, ref_snapshot, generate x2, score, step, triage x2
    assert got == baseline_digests


def test_an_edited_source_file_changes_the_key(tmp_path):
    pkg = tmp_path / "pkg"
    (pkg / "sub" / "__pycache__").mkdir(parents=True)
    (pkg / "a.py").write_text("X = 1\n")
    (pkg / "sub" / "b.py").write_text("Y = 2\n")
    first = programs.tree_digest(str(pkg))
    (pkg / "sub" / "__pycache__" / "b.cpython-312.pyc").write_bytes(b"\0")
    assert programs.tree_digest(str(pkg)) == first  # what the interpreter leaves behind is not source
    (pkg / "sub" / "b.py").write_text("Y = 3\n")
    edited = programs.tree_digest(str(pkg))
    assert edited != first
    (pkg / "sub" / "b.py").rename(pkg / "sub" / "c.py")
    assert programs.tree_digest(str(pkg)) not in (first, edited)
    # the package's own digest is in every job's key, and is of this checkout's files
    assert programs.package_digest() == programs.tree_digest(os.path.join(REPO, "trlx_tpu"))
    assert f"package {programs.package_digest()}" in ProgramStore()._parts


def test_a_class_outside_the_package_enters_the_key_by_its_source(tmp_path, monkeypatch):
    monkeypatch.syspath_prepend(str(tmp_path))
    (tmp_path / "user_trainer_mod.py").write_text("class Mine:\n    X = 1\n")
    import user_trainer_mod

    first = programs.class_sources([user_trainer_mod.Mine, dict, TrainState])
    assert first is not None and first != programs.class_sources([TrainState])
    (tmp_path / "user_trainer_mod.py").write_text("class Mine:\n    X = 2\n")
    assert programs.class_sources([user_trainer_mod.Mine]) != first
    assert str(tmp_path) not in "".join(ProgramStore(classes=[user_trainer_mod.Mine])._parts)


def test_a_class_defined_in_main_or_without_a_file_is_never_stored():
    in_main = type("InMain", (), {"__module__": "__main__"})
    scope = {}
    exec("class FromNowhere:\n    pass\n", {"__name__": "made_up_module"}, scope)
    for cls in (in_main, scope["FromNowhere"]):
        assert programs.class_sources([TrainState, cls]) is None
        store = ProgramStore(classes=[TrainState, cls])
        assert not store.stored
        before = _counts()
        program = store.program("inc", lambda x: x + 1)
        np.testing.assert_array_equal(program(jnp.zeros(2)), np.ones(2))
        assert _since(before) == {"hits": 0, "misses": 0} and _entries() == []
        assert program._cache_size() == 1  # the plain jit's own count
    # and a store that met such a class later stops keeping from there on
    store = ProgramStore(classes=[TrainState])
    assert store.stored
    store.extend(classes=[in_main])
    assert not store.stored


def test_the_key_holds_no_path_no_seed_and_the_mesh(tmp_path):
    from trlx_tpu.parallel.mesh import make_mesh

    a = _toy(seed=1, checkpoint_dir=str(tmp_path / "a"))
    b = _toy(seed=2, checkpoint_dir=str(tmp_path / "b"), logging_dir=str(tmp_path / "logs"))
    mesh = make_mesh(a.parallel)
    parts = ProgramStore(a, mesh=mesh)._parts
    assert parts == ProgramStore(b, mesh=mesh)._parts
    text = "\n".join(parts)
    assert str(tmp_path) not in text and REPO not in text and os.getcwd() not in text
    assert programs.describe_mesh(mesh) in parts and "jax " + jax.__version__ in text
    other = make_mesh(a.evolve(parallel=dict(data=2, fsdp=2, model=2)).parallel)
    assert ProgramStore(a, mesh=other)._parts != parts
    assert programs.array_bytes(np.eye(3, dtype=bool)) != programs.array_bytes(np.ones((3, 3), bool))


# ---------------------------------------------------------------------------
# a whole job, twice
# ---------------------------------------------------------------------------

JOB = """
import json, sys
import trlx_tpu
from trlx_tpu.data.default_configs import default_ppo_config
from trlx_tpu.observability import tracing
from trlx_tpu.utils import programs

seed, root = int(sys.argv[1]), sys.argv[2]
config = default_ppo_config().evolve(
    train=dict(seq_length=24, batch_size=8, total_steps=6, epochs=3, tracker=None, seed=seed,
               checkpoint_dir=root + "/ckpts" + str(seed), eval_interval=1000,
               checkpoint_interval=1000),
    model=dict(model_path="builtin:gpt2-test", num_layers_unfrozen=1),
    tokenizer=dict(tokenizer_path="builtin:bytes"),
    method=dict(num_rollouts=16, chunk_size=8, ppo_epochs=1,
                gen_kwargs=dict(max_new_tokens=8, top_k=0, top_p=1.0, do_sample=True)),
)
records = []


class Keep:
    def log(self, stats, step=None):
        records.append(dict(stats))

    def finish(self):
        pass


def hook(trainer):
    trainer.tracker = Keep()


trainer = trlx_tpu.train(
    reward_fn=lambda samples, **kw: [float(len(s)) for s in samples],
    prompts=["hello world"] * 16, eval_prompts=["hello"] * 8, config=config,
    init_trainer_hook=hook)
gauges = {k: v for r in records for k, v in r.items() if k.startswith("setup/")}
print(json.dumps({
    "gauges": gauges,
    "rows": {fun: row for fun, row in tracing.programs().items()},
    "compared": programs.verify(trainer),
    "loss": [r["losses/total_loss"] for r in records if "losses/total_loss" in r],
}))
"""

JOBS_OWN = {"make_params", "init_state", "ref_snapshot", "rollout_generate", "score_fn",
            "train_step", "response_logprobs", "get_advantages_and_returns"}


def test_a_second_process_loads_every_program_and_traces_none(tmp_path):
    script = tmp_path / "job.py"
    script.write_text(textwrap.dedent(JOB))
    env = dict(os.environ, PYTHONPATH=REPO, JAX_PLATFORMS="cpu", TRLX_TPU_MFU="0",
               JAX_COMPILATION_CACHE_DIR=str(tmp_path / "cache"))

    def run(seed):
        p = subprocess.run([sys.executable, str(script), str(seed), str(tmp_path)], env=env,
                           capture_output=True, text=True, timeout=600)
        assert p.returncode == 0, p.stderr[-3000:]
        return json.loads(p.stdout.strip().splitlines()[-1]), p.stderr + p.stdout

    cold, _ = run(1)
    assert cold["gauges"]["setup/store_hits"] == 0 and cold["gauges"]["setup/store_hit_pct"] == 0
    assert cold["gauges"]["setup/store_misses"] == 9 and cold["compared"] == 0
    assert JOBS_OWN <= {f for f, row in cold["rows"].items() if row.get("runtime/trace")}
    assert os.path.isdir(tmp_path / "cache" / "programs")  # inside the compile cache's directory

    warm, log = run(2)  # another seed: an argument of the programs, not a part of their key
    g = warm["gauges"]
    assert g["setup/store_hits"] == 9 and g["setup/store_misses"] == 0
    assert g["setup/store_hit_pct"] == 100 and g["setup/store_load_s"] > 0
    assert "9 loaded without a trace" in log and "0 compiled in all" in log
    for fun in JOBS_OWN:  # no jaxpr_trace, lowering or backend_compile event for any of them
        row = warm["rows"][fun]
        assert row.get("runtime/store_load", 0) > 0, fun
        assert not any(row.get(k) for k in ("runtime/trace", "runtime/lower", "runtime/compile",
                                            "runtime/cache_load", "programs")), (fun, row)
    assert g["setup/trace_lower_s"] < cold["gauges"]["setup/trace_lower_s"] / 4
    assert warm["compared"] == 9  # and each loaded entry's digest is a fresh lowering's
    assert all(np.isfinite(warm["loss"])) and warm["loss"] != cold["loss"]
