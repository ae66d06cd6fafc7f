"""The program store: a warm start loads the job's own compiled programs
without tracing them.

JAX's persistent compile cache is keyed on the lowered module, so a warm start
spares the compile and still pays the trace and the lowering that produce the
key: the largest single part of set-up in every cell (PERF.md section 6,
PR 50). A :class:`StoredProgram` has ``jax.jit``'s calling convention and keeps
each argument signature's compiled executable on disk under a key that needs
no trace: what the code can observe of the job (:class:`ProgramStore`) and the
call's argument signature. On a hit the executable is deserialized and called;
on a miss the program is traced, lowered and compiled exactly as ``jax.jit``
would, and the executable is written for the next start.

**The key** holds a digest of every source file of the package and of the
job's classes that live outside it, the versions of jax, jaxlib, flax, optax
and numpy, the backend's own version string, the device kind and count, the
mesh, ``XLA_FLAGS`` / ``LIBTPU_INIT_ARGS``, the jax flags that change a trace,
the whole ``TRLConfig`` less :data:`EXCLUDED_CONFIG_FIELDS`, the model's
``TransformerConfig``, the site's own memo key with the bytes of any array its
closure bakes in, and the argument signature. It holds no path, no seed and no
time. A class whose source file cannot be found (``__main__``, a notebook)
turns the store off for its job: the programs are then plain ``jax.jit``.

**The guard against a stale hit**: a key that is not the program can go stale
where the program changed and nothing the key reads did (a constant patched in
place). Every entry keeps the SHA-256 of the lowered StableHLO text it was
compiled from; :func:`verify` traces and lowers every program a trainer loaded
and compares. It is what the tests and a builder's chip run call, never a job:
it is the cost the store removes.

**Where**: ``programs/`` inside the directory JAX's persistent cache uses
(:func:`store_dir`), so ``rm -rf .jax_cache`` still makes a start cold. A
failure to read, unpickle or load an entry is a miss that rewrites it. The
store engages in single-process jobs only.
"""

import hashlib
import inspect
from contextlib import contextmanager
import os
import pickle
import sys
import tempfile
import threading
import time
from functools import lru_cache
from typing import Any, Callable, Dict, Iterable, List, NamedTuple, Optional, Sequence, Tuple

import jax
import numpy as np

from trlx_tpu.observability import tracing
from trlx_tpu.utils import flatten_dict, logging

logger = logging.get_logger(__name__)

FORMAT = 2  # of an entry's file

try:  # as JAX's own cache does: zstandard where it is installed
    import zstandard

    _CODEC = "zstd"

    def _compress(data: bytes) -> bytes:
        return zstandard.ZstdCompressor(level=3).compress(data)

    def _decompress(data: bytes) -> bytes:
        return zstandard.ZstdDecompressor().decompress(data)

except ImportError:  # pragma: no cover - the container has zstandard
    import zlib

    _CODEC = "zlib"
    _compress, _decompress = zlib.compress, zlib.decompress

# TRLConfig fields no program reads, by dotted path: the seed is an ARGUMENT of
# every program that uses it (trainer/base.py::init_state,
# models/builder.py::_build_params), the others name where the host writes and
# what it calls the run. A field in doubt stays in the key; each of these is
# proven by tests/test_program_store.py (every program's lowered text is
# byte-identical under another value).
EXCLUDED_CONFIG_FIELDS = (
    "train.seed",
    "train.checkpoint_dir",
    "train.logging_dir",
    "train.rollout_logging_dir",
    "train.tracker",
    "train.project_name",
    "train.entity_name",
    "train.group_name",
    "train.tags",
)

# jax flags that change what a function traces or lowers to
_TRACE_FLAGS = (
    "jax_enable_x64",
    "jax_default_matmul_precision",
    "jax_default_prng_impl",
    "jax_threefry_partitionable",
    "jax_numpy_dtype_promotion",
    "jax_numpy_rank_promotion",
)

_PACKAGE_ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def store_dir() -> Optional[str]:
    """The store's root: ``programs/`` in the directory JAX's persistent
    compile cache uses (``JAX_COMPILATION_CACHE_DIR``, else what
    ``trlx.initialize_runtime()`` configured), or None where there is no such
    directory: nothing is then read or written. The one seam the test suite
    patches (``tests/conftest.py`` gives every test a store of its own)."""
    root = os.environ.get("JAX_COMPILATION_CACHE_DIR") or jax.config.jax_compilation_cache_dir
    return os.path.join(root, "programs") if root else None


# ---------------------------------------------------------------------------
# the key
# ---------------------------------------------------------------------------


def _sha(*chunks: bytes) -> str:
    h = hashlib.sha256()
    for c in chunks:
        h.update(len(c).to_bytes(8, "little"))
        h.update(c)
    return h.hexdigest()


def tree_digest(root: str) -> str:
    """SHA-256 over every source file under ``root``: relative name and bytes,
    in sorted order (byte-compiled files and caches apart)."""
    chunks: List[bytes] = []
    for d, dirs, files in os.walk(root):
        dirs[:] = sorted(x for x in dirs if x != "__pycache__")
        for name in sorted(files):
            if name.endswith((".pyc", ".pyo", ".so")):
                continue
            path = os.path.join(d, name)
            with open(path, "rb") as f:
                chunks += [os.path.relpath(path, root).encode(), f.read()]
    return _sha(*chunks)


@lru_cache(maxsize=None)
def package_digest() -> str:
    """The package's own sources, read once a process."""
    return tree_digest(_PACKAGE_ROOT)


def class_sources(classes: Iterable[type]) -> Optional[str]:
    """Digest of the source files of ``classes`` that live outside the package
    (a trainer registered from a user's script, a method's config class, a
    model module): module name and bytes, no path. ``None`` where one has no
    file to read: its programs are then not stored."""
    files: Dict[str, str] = {}
    for cls in classes:
        if cls.__module__ in ("builtins", "abc", "typing"):
            continue
        module = sys.modules.get(cls.__module__)
        try:
            path = inspect.getsourcefile(cls)
        except (TypeError, OSError):
            path = None
        if cls.__module__ == "__main__" or module is None or not path or not os.path.isfile(path):
            return None
        path = os.path.abspath(path)
        if path.startswith(_PACKAGE_ROOT + os.sep) or _installed(path):
            continue  # the package digest, or a version in environment()
        files[cls.__module__] = path
    chunks: List[bytes] = []
    for name in sorted(files):
        with open(files[name], "rb") as f:
            chunks += [name.encode(), f.read()]
    return _sha(*chunks)


def _installed(path: str) -> bool:
    return f"{os.sep}site-packages{os.sep}" in path or f"{os.sep}dist-packages{os.sep}" in path


@lru_cache(maxsize=None)
def environment() -> Tuple[str, ...]:
    """What of the process decides a compiled program, beside the job."""
    import flax
    import jaxlib
    import optax

    dev = jax.devices()[0]
    return (
        f"jax {jax.__version__} jaxlib {jaxlib.__version__} flax {flax.__version__} "
        f"optax {optax.__version__} numpy {np.__version__}",
        # the runtime's own version string: libtpu's build on a TPU
        f"{dev.platform} {dev.client.platform_version} {dev.device_kind} x{jax.device_count()}",
        f"XLA_FLAGS={os.environ.get('XLA_FLAGS', '')}",
        f"LIBTPU_INIT_ARGS={os.environ.get('LIBTPU_INIT_ARGS', '')}",
    )


def _trace_flags() -> str:
    return " ".join(f"{name}={getattr(jax.config, name, None)!r}" for name in _TRACE_FLAGS)


def config_fields(config: Any) -> Dict[str, str]:
    """``TRLConfig.to_dict()`` flattened to dotted paths, values as ``repr``,
    less :data:`EXCLUDED_CONFIG_FIELDS`: the part of the key the job's
    configuration gives."""
    flat = flatten_dict(config.to_dict(), sep=".")
    return {k: repr(v) for k, v in sorted(flat.items()) if k not in EXCLUDED_CONFIG_FIELDS}


def describe_mesh(mesh: Any) -> str:
    if mesh is None:
        return "mesh None"
    ids = ",".join(str(d.id) for d in np.asarray(mesh.devices).flat)
    return f"mesh {dict(mesh.shape)!r} {tuple(mesh.axis_names)!r} devices {ids}"


def array_bytes(x: Any) -> str:
    """Key part for an array a program's closure bakes in (a transition mask,
    token ids): its shape, dtype and a digest of its bytes."""
    if x is None:
        return "None"
    a = np.asarray(x)
    return f"{a.shape} {a.dtype} {_sha(np.ascontiguousarray(a).tobytes())}"


def _leaf_signature(x: Any) -> Any:
    if isinstance(x, jax.Array):
        if isinstance(x, jax.core.Tracer):
            raise _Plain
        return (x.shape, x.dtype, x.weak_type, x.sharding, x.committed)
    if isinstance(x, (np.ndarray, np.generic)):
        return (x.shape, x.dtype)
    if isinstance(x, (bool, int, float, complex)):
        return type(x)  # weakly typed: the value is an argument
    if isinstance(x, jax.ShapeDtypeStruct):
        return (x.shape, x.dtype, getattr(x, "weak_type", False), x.sharding)
    raise _Plain  # not a thing this module knows how to key: trace as jax.jit would


class _Plain(Exception):
    """This call goes to the plain ``jax.jit``."""


_LOWERING = threading.RLock()  # one thread at a time changes the limit and puts it back


@contextmanager
def _no_call_stack():
    """Lower without the Python call stack in the locations. A Mosaic kernel's
    serialized body carries its locations, frames of the CALLERS of the jitted
    function among them wherever the kernel sits fewer than ten frames deep
    (cell 10's programs, PR 50): the same program lowered under ``verify``
    and under a collection then differs in text, and holds the checkout's
    path. Without frames the text is a function of the program alone."""
    name = "jax_traceback_in_locations_limit"
    with _LOWERING:
        keep = getattr(jax.config, name)
        jax.config.update(name, 0)
        try:
            yield
        finally:
            jax.config.update(name, keep)


# ---------------------------------------------------------------------------
# one program
# ---------------------------------------------------------------------------


class ProgramBytes(NamedTuple):
    """What the compiler says one executable holds on a device
    (``Compiled.memory_analysis()``, read once when the executable is made or
    loaded: no trace and no compile). ``peak`` is the compiler's own
    ``peak_memory_in_bytes`` where the runtime gives one above 0, else 0."""

    code: int
    arguments: int
    outputs: int
    aliased: int
    temp: int
    peak: int

    @property
    def live(self) -> int:
        """Bytes of buffers a run touches: arguments, and the outputs that are
        no donated argument's buffer."""
        return self.arguments + self.outputs - self.aliased

    @classmethod
    def of(cls, compiled: Any) -> Optional["ProgramBytes"]:
        try:
            m = compiled.memory_analysis()
            return cls(int(m.generated_code_size_in_bytes), int(m.argument_size_in_bytes),
                       int(m.output_size_in_bytes), int(m.alias_size_in_bytes),
                       int(m.temp_size_in_bytes),
                       max(int(getattr(m, "peak_memory_in_bytes", 0) or 0), 0))
        except Exception as e:  # a runtime without the analysis: the account has no row
            logger.debug("program store: no memory analysis (%s: %s)", type(e).__name__, e)
            return None


class _Held:
    """One signature's executable, what :func:`verify` needs of it, and its
    row of bytes (``None`` where the runtime has no memory analysis)."""

    __slots__ = ("call", "loaded", "digest", "path", "abstract", "bytes")

    def __init__(self, call, loaded, digest, path, abstract):
        self.call, self.loaded, self.digest, self.path, self.abstract = (
            call, loaded, digest, path, abstract)
        self.bytes = ProgramBytes.of(call)


class StoredProgram:
    """``jax.jit(fn, **jit_kwargs)`` whose executables outlive the process.

    ``key_parts`` is the part of the key that is not the call's signature (a
    sequence of strings), or ``None``: the program is then a plain ``jax.jit``
    (a class without a source file, a job of several processes, no store
    directory). ``jit_kwargs`` may be a function of no arguments that returns
    them: it runs on a miss only, so that what a kwarg costs to compute
    (``out_shardings`` from a ``jax.eval_shape`` of the function) is not paid
    on a hit. ``once`` says the program runs once a job (``make_params``): its
    executable is let go after each call, as a ``jax.jit`` dropped after its
    call lets go of its own, and the code leaves the device's memory.

    A call finds its executable by the tree and the shapes of its arguments
    alone (a tenth of a millisecond for a train state of hundreds of leaves)
    and leaves dtypes, shardings and placement to the executable's own check,
    which refuses before anything runs; a refusal, like a first call, takes the
    whole signature."""

    def __init__(self, name: str, fn: Callable, key_parts: Optional[Sequence[str]],
                 jit_kwargs: Any = None, once: bool = False):
        self.name = name
        self._once = once
        self._fn = fn
        self._jit_kwargs = jit_kwargs or {}
        self._jit_fn: Optional[Callable] = None
        self._key = None if key_parts is None else _sha(*(str(p).encode() for p in key_parts))
        self._held: Dict[Any, _Held] = {}
        self._by_shape: Dict[Any, _Held] = {}  # (tree, shapes) -> the newest signature's
        self._plain = 0  # programs the plain jax.jit compiled for calls that came to it
        self._lock = threading.Lock()

    # -- jax.jit's surface ------------------------------------------------

    @property
    def _jit(self) -> Callable:
        if self._jit_fn is None:
            kwargs = self._jit_kwargs() if callable(self._jit_kwargs) else self._jit_kwargs
            self._jit_fn = jax.jit(self._fn, **kwargs)
        return self._jit_fn

    def lower(self, *args: Any, **kwargs: Any):
        """The inner ``jax.jit``'s lowering (the MFU gauge, memory analyses)."""
        return self._jit.lower(*args, **kwargs)

    def _cache_size(self) -> int:
        """Signatures held, loaded or compiled alike, plus whatever went to
        the plain ``jax.jit``: the recompile watchdog reads a program's growth
        here as it does a ``jax.jit``'s."""
        return len(self._held) + self._plain

    def __getattr__(self, attr: str) -> Any:  # whatever else a jax.jit answers
        if attr.startswith("__"):
            raise AttributeError(attr)
        return getattr(self._jit, attr)

    def _call_plain(self, args: Any, kwargs: Any):
        # a lowering leaves an entry in the jit's cache too: count what calls add
        jit = self._jit
        before = jit._cache_size()
        try:
            return jit(*args, **kwargs)
        finally:
            self._plain += jit._cache_size() - before

    def __call__(self, *args: Any, **kwargs: Any):
        if self._key is None or jax.config.jax_disable_jit:
            return self._call_plain(args, kwargs)
        leaves, treedef = jax.tree_util.tree_flatten((args, kwargs))
        try:
            shapes = (treedef, tuple([x.shape for x in leaves]))
        except AttributeError:  # Python scalars among the leaves
            shapes = (treedef, tuple([getattr(x, "shape", None) for x in leaves]))
        held = self._by_shape.get(shapes)
        if held is not None and held.call is not None:
            try:
                return held.call(*args, **kwargs)
            except (TypeError, ValueError):
                pass  # the same shapes under another dtype, sharding or placement; a tracer
        try:
            signature = (treedef, tuple(map(_leaf_signature, leaves)))
            held = self._held.get(signature)
        except (_Plain, TypeError):  # a tracer, a foreign leaf, an unhashable one
            return self._call_plain(args, kwargs)
        if held is None or held.call is None:
            held = self._first_call(signature, args, kwargs)
            if held is None:
                return self._call_plain(args, kwargs)
        call = held.call
        if self._once:  # with the inner jit, which holds what it compiled
            held.call = self._jit_fn = None
        else:
            self._by_shape[shapes] = held
        return call(*args, **kwargs)

    # -- the store ----------------------------------------------------------

    def _first_call(self, signature: Any, args: Any, kwargs: Any) -> Optional[_Held]:
        with self._lock:
            held = self._held.get(signature)
            if held is not None and held.call is not None:
                return held
            root = store_dir()
            if root is None:
                return None
            text = repr((str(signature[0]), signature[1]))
            path = os.path.join(root, f"{self.name}-{_sha(self._key.encode(), text.encode())[:40]}.bin")
            abstract = jax.tree_util.tree_map(_abstract, (args, kwargs))
            held = self._load(path, abstract)
            if held is None:
                held = self._compile_and_write(path, args, kwargs, abstract)
            self._held[signature] = held
            return held

    def _load(self, path: str, abstract: Any) -> Optional[_Held]:
        from jax.experimental import serialize_executable

        t0 = time.perf_counter()
        try:
            with open(path, "rb") as f:
                entry = pickle.load(f)
            if (entry["format"], entry["codec"], entry["key"]) != (FORMAT, _CODEC, self._key):
                raise ValueError("another format, codec or key")
            by_id = {d.id: d for d in jax.devices()}
            compiled = serialize_executable.deserialize_and_load(
                _decompress(entry["executable"]), entry["in_tree"], entry["out_tree"],
                execution_devices=[by_id[i] for i in entry["devices"]])
        except FileNotFoundError:
            return None
        except Exception as e:  # truncated, garbage, another runtime's: a miss that rewrites it
            logger.warning("program store: %s is unreadable (%s: %s); compiling it again",
                           os.path.basename(path), type(e).__name__, e)
            return None
        tracing.count("runtime/store_hits")
        tracing.attribute("runtime/store_load", t0, time.perf_counter(), fun_name=self.name)
        return _Held(compiled, True, entry["stablehlo_sha256"], path, abstract)

    def _compile_and_write(self, path: str, args: Any, kwargs: Any, abstract: Any) -> _Held:
        from jax.experimental import serialize_executable

        tracing.count("runtime/store_misses")
        tracing.install_sources()
        with _no_call_stack():
            lowered = self._jit.lower(*args, **kwargs)
        cache_hits = tracing.thread_cache_hits()
        compiled = lowered.compile()
        t0 = time.perf_counter()
        digest = _sha(lowered.as_text().encode())
        try:
            devices = compiled.runtime_executable().local_devices()
            if tracing.thread_cache_hits() > cache_hits and devices[0].platform != "tpu":
                # XLA:CPU serializes an executable it deserialized without its
                # object code: the entry loads, and its first call fails
                # (NOT_FOUND). A TPU's serializes whole (PERF.md section 6, PR 50).
                # The executable's own devices say where it runs: tests and
                # rehearsals patch jax.default_backend
                raise ValueError("its executable came from the persistent compile cache, which "
                                 f"the {devices[0].platform} runtime cannot serialize again")
            executable, in_tree, out_tree = serialize_executable.serialize(compiled)
            entry = {"format": FORMAT, "codec": _CODEC, "key": self._key, "name": self.name,
                     "stablehlo_sha256": digest, "executable": _compress(executable),
                     # the devices it runs on, in its own order: one of a mesh of eight
                     "devices": [d.id for d in devices],
                     "in_tree": in_tree, "out_tree": out_tree}
            os.makedirs(os.path.dirname(path), exist_ok=True)
            # a temporary of this writer's own, then a rename: two processes
            # that write one entry leave one whole file
            fd, tmp = tempfile.mkstemp(dir=os.path.dirname(path), prefix=".writing-")
            try:
                with os.fdopen(fd, "wb") as f:
                    pickle.dump(entry, f, protocol=pickle.HIGHEST_PROTOCOL)
                os.replace(tmp, path)
            except BaseException:
                os.unlink(tmp)
                raise
        except Exception as e:  # constants in the closure, a full disk: run it, keep nothing
            logger.debug("program store: %s is not kept (%s: %s)", self.name, type(e).__name__, e)
        tracing.attribute("runtime/store_write", t0, time.perf_counter(), fun_name=self.name)
        return _Held(compiled, False, digest, path, abstract)

    # -- the guard ----------------------------------------------------------

    def verify(self) -> List[str]:
        """Faults of the entries this program LOADED: each is traced and
        lowered afresh and its StableHLO digest compared with the entry's."""
        faults = []
        for held in list(self._held.values()):
            if not held.loaded:
                continue
            args, kwargs = held.abstract
            with _no_call_stack():
                fresh = _sha(self._jit.lower(*args, **kwargs).as_text().encode())
            if fresh != held.digest:
                faults.append(
                    f"{self.name}: {os.path.basename(held.path)} was compiled from StableHLO "
                    f"{held.digest[:16]}, a fresh lowering gives {fresh[:16]}")
        return faults

    def loaded(self) -> int:
        return sum(h.loaded for h in self._held.values())

    def rows(self) -> Tuple[List[ProgramBytes], bool]:
        """The rows of the executables this program holds now (a ``once``
        program's has gone with its call), and whether it ever had one."""
        held = [h for h in list(self._held.values()) if h.bytes is not None]
        return [h.bytes for h in held if h.call is not None], bool(held)


def _abstract(x: Any) -> Any:
    """A leaf's twin for a later ``lower``: no buffer is kept alive."""
    if isinstance(x, jax.Array):
        # an uncommitted array lowers as a shape with no sharding does
        return jax.ShapeDtypeStruct(x.shape, x.dtype, weak_type=x.weak_type,
                                    sharding=x.sharding if x.committed else None)
    if isinstance(x, (np.ndarray, np.generic)):
        return jax.ShapeDtypeStruct(x.shape, x.dtype)
    return x


def stored_program(name: str, fn: Callable, key_parts: Optional[Sequence[str]],
                   jit_kwargs: Any = None, once: bool = False,
                   **more_jit_kwargs: Any) -> StoredProgram:
    """``jax.jit(fn, **jit_kwargs)`` kept by ``key_parts`` (see
    :class:`StoredProgram`). Under several processes every program is plain."""
    if key_parts is not None and jax.process_count() > 1:
        key_parts = None
    if more_jit_kwargs:
        jit_kwargs = dict(jit_kwargs or {}, **more_jit_kwargs)
    return StoredProgram(name, fn, key_parts, jit_kwargs, once)


# ---------------------------------------------------------------------------
# one job's programs
# ---------------------------------------------------------------------------


class ProgramStore:
    """The programs of one job (a trainer holds one as ``trainer.programs``)
    and the part of their key the job gives. ``extend`` adds what becomes
    known as the trainer is built (the model's classes and its
    ``TransformerConfig``); a class without a source file turns the store off
    for every program made from then on."""

    def __init__(self, config: Any = None, classes: Iterable[type] = (), mesh: Any = None):
        self._parts: Optional[List[str]] = [
            f"format {FORMAT}", *environment(), _trace_flags(), f"package {package_digest()}",
            describe_mesh(mesh),
        ]
        if config is not None:
            self._parts += [f"{k}={v}" for k, v in config_fields(config).items()]
        self._programs: List[StoredProgram] = []
        self.extend(classes=classes)

    @property
    def stored(self) -> bool:
        return self._parts is not None

    def extend(self, *parts: Any, classes: Iterable[type] = ()) -> None:
        sources = class_sources(classes)
        if sources is None:
            self._parts = None
        if self._parts is not None:
            self._parts += [f"classes {sources}", *map(str, parts)]

    def program(self, name: str, fn: Callable, *parts: Any, jit_kwargs: Any = None,
                once: bool = False, **more_jit_kwargs: Any) -> StoredProgram:
        """A program of this job: ``parts`` is the site's own memo key."""
        key = None if self._parts is None else [*self._parts, name, *map(str, parts)]
        p = stored_program(name, fn, key, jit_kwargs, once, **more_jit_kwargs)
        self._programs.append(p)
        return p

    def verify(self) -> List[str]:
        return [fault for p in self._programs for fault in p.verify()]

    def loaded(self) -> int:
        return sum(p.loaded() for p in self._programs)

    def account(self) -> Optional[Dict[str, Any]]:
        """What the job's executables hold on a device now. ``by_program``:
        name -> executables resident, their code bytes summed, the largest
        temporaries and the largest arguments + outputs - aliased among them
        (one name can hold several signatures and several sites); ``code`` and
        ``resident`` are its sums, ``temp`` its largest temporaries, of
        ``temp_program``, with the compiler's own peak of that executable.
        ``None`` where no program of the job ever had a row (the plain
        ``jax.jit`` path: no store directory, several processes, a class
        without a source file)."""
        by_program: Dict[str, List[int]] = {}
        out: Dict[str, Any] = {"temp": 0, "temp_program": "", "temp_program_peak": 0}
        for p in self._programs:
            rows, ever = p.rows()
            if not ever:
                continue
            mine = by_program.setdefault(p.name, [0, 0, 0, 0])
            for row in rows:
                mine[:] = mine[0] + 1, mine[1] + row.code, max(mine[2], row.temp), max(mine[3], row.live)
                if row.temp >= out["temp"]:
                    out.update(temp=row.temp, temp_program=p.name, temp_program_peak=row.peak)
        if not by_program:
            return None
        out.update(by_program=by_program, resident=sum(r[0] for r in by_program.values()),
                   code=sum(r[1] for r in by_program.values()))
        return out


def verify(trainer: Any) -> int:
    """Trace and lower every program ``trainer``'s job loaded from the store
    and compare each with the digest its entry recorded. Returns how many were
    compared; raises ``RuntimeError`` naming every entry that is stale."""
    store: ProgramStore = trainer.programs
    faults = store.verify()
    if faults:
        raise RuntimeError("program store: stale entries (remove them, or the whole "
                           f"{store_dir()}):\n" + "\n".join(faults))
    return store.loaded()
