"""The program store: a warm start LOADS its programs.

JAX's persistent compile cache keeps executables, keyed by the lowered
module: a start that finds every executable there still runs each program's
Python (the trace) and its lowering to get the key, and that was 7-53 s of
a 28-92 s start (PERF.md §5 "Set-up"). The store keeps a BUILT program,
its executable serialised by ``jax.experimental.serialize_executable``,
under a key digested from what the program was built FROM, so the next
start of the same tree with the same configuration and shapes loads it and
runs neither stage. (The other form measured, a ``jax.export`` module
behind ``jax.jit``, keeps JAX's dispatch and pays the lowering again:
CHANGES.md PR 61 has the table.)

One wrapper, :func:`stored_jit`, stands where ``jax.jit`` stood in the
engines' builders and is called exactly as the jitted function is::

    jitted = program_store.stored_jit(
        chunk, name="gen/chunk", key=key, built_from=self._built_from(),
        donate_argnums=(1,), **sharding_kw)

* **Where.** ``compile_cache.configure()`` opens the store in a directory
  of its own INSIDE the cache's (``<cache>/programs``: what keeps the
  cache between runs keeps the store; JAX's least-recently-used sweep
  globs ``*-cache`` at the top level only and leaves a subdirectory alone,
  ``tests/test_program_store.py`` holds it to that). A process that never
  configured a cache (the test session, a CPU run that asked for none) has
  no store: ``stored_jit`` IS ``jax.jit`` there. Nothing to set.
* **The key** is a digest of: the program's name and static key; what the
  builder says it was built from (the ``ModelConfig``, every engine setting
  the closure reads, a caller's function and the optimizer by
  :func:`fingerprint`: a function by its name and by what its closure and
  its defaults HOLD, the hyper-parameters a loss function or an update
  bakes into its program; its text is in the source digest, or the
  installation's); the ``jax.jit`` options; of
  every argument the tree, shape, dtype, weak type and sharding (known at
  the first call); the package's source (``areal_tpu/**/*.py``, once a
  process); the versions of jax, jaxlib and the device runtime; device
  kind and count, process index and count; every ``jax.config`` value but
  where the cache lives, and the environment that may shape a program
  (``constants.program_env``: every ``AREAL_*`` variable but those that
  say where files live, who talks to whom and what is logged). A false hit
  would run the wrong program silently and a miss costs what a start cost
  before: where in doubt, miss. What cannot be keyed (a function from
  outside the package and the installation, whose text no digest holds; a
  closure over an object without a stable text) or cannot be serialised (a
  multi-process world) stays on ``jax.jit``'s own path by that test, never
  by a flag; so does a process that dumps every program's lowering
  (``JAX_DUMP_IR_TO``: ``chip_smoke.py`` reads the kernels' names there,
  and drives a warm start apart, without the dump).
* **A miss** builds as before (``lower`` then ``compile``: JAX's own
  events book it) and hands the executable to ONE writer thread, off the
  start's critical path: serialise, compress (zstd, as JAX's cache does
  where it is installed), write to a temporary name, rename. Two processes may share the directory. An entry that is missing,
  unreadable, of another key or that fails to load is a miss and is written
  again. The directory is held under the bound the cache obeys
  (``JAX_COMPILATION_CACHE_MAX_SIZE``), least recently used out.
* **A hit** is booked as a program built from a cache
  (``tracing.program_loaded``): ``compile/programs`` +1,
  ``compile/cache_hits`` +1, ``compile/backend_s`` its load, no trace or
  lowering seconds, a ``compile/program`` record with ``stored: true``.
  ``compile/store_hits`` and ``compile/store_misses`` say how often the
  mechanism engaged.
* **A call** goes to the loaded ``jax.stages.Compiled``, whose C++ fast
  path checks the arguments as ``jax.jit``'s does; the arguments it
  refuses (``TypeError`` / ``ValueError``, before anything runs) are a new
  specialisation, resolved like the first. ``_cache_size()`` counts the
  specialisations, so ``n_jit_entries()`` of both engines reads what it
  read (``base/jitcache.py``).
"""

import atexit
import dataclasses
import functools
import hashlib
import os
import pickle
import queue
import sys
import sysconfig
import threading
import time
import types
from typing import Any, Callable, Dict, Optional

import numpy as np

from areal_tpu.base import constants, jitcache, logging
from areal_tpu.base import tracing

logger = logging.getLogger("program_store")

SUBDIR = "programs"
SUFFIX = ".program"
_MAGIC = b"areal-program-2\n"

try:    # what JAX's own cache compresses with where it is installed
    import zstandard
except ImportError:
    zstandard = None

_dir: Optional[str] = None
_writer: Optional["_Writer"] = None
_lock = threading.Lock()


# --------------------------------------------------------------------- #
# Where
# --------------------------------------------------------------------- #


def open_in(cache_dir: Optional[str]) -> Optional[str]:
    """The store lives in ``<cache_dir>/programs``; ``None`` closes it.
    ``compile_cache.configure`` calls this with what it returns."""
    global _dir
    _dir = None if cache_dir is None else os.path.join(cache_dir, SUBDIR)
    return _dir


def directory() -> Optional[str]:
    return _dir


def max_bytes() -> int:
    """The bound the compile cache obeys (``jax_compilation_cache_max_size``,
    from ``JAX_COMPILATION_CACHE_MAX_SIZE``): -1 none, 0 store nothing."""
    import jax

    return int(jax.config.jax_compilation_cache_max_size)


# --------------------------------------------------------------------- #
# The key
# --------------------------------------------------------------------- #


class Unkeyable(Exception):
    """Something the program was built from has no stable text."""


def _stable(obj: Any, seen: tuple = ()) -> Any:
    """``obj`` as nested tuples of text whose ``repr`` is the same in every
    process that built it from the same source and settings; raises
    :class:`Unkeyable` where it cannot vouch for that."""
    if obj is None or isinstance(obj, (bool, int, float, complex, str, bytes)):
        return obj
    if id(obj) in seen:
        return ("again", type(obj).__qualname__)
    if len(seen) > 24:
        raise Unkeyable("nested too deep")
    seen += (id(obj),)
    sub = functools.partial(_stable, seen=seen)
    if isinstance(obj, (tuple, list)):
        return (type(obj).__name__,) + tuple(sub(x) for x in obj)
    if isinstance(obj, (set, frozenset)):
        return ("set",) + tuple(sorted(repr(sub(x)) for x in obj))
    if isinstance(obj, dict):
        return ("dict",) + tuple(sorted(
            (repr(sub(k)), sub(v)) for k, v in obj.items()))
    if isinstance(obj, (np.ndarray, np.generic)):
        a = np.asarray(obj)
        return ("ndarray", str(a.dtype), a.shape,
                hashlib.sha256(np.ascontiguousarray(a).tobytes()).hexdigest())
    if isinstance(obj, np.dtype) or (
            isinstance(obj, type) and issubclass(obj, np.generic)):
        return ("dtype", str(np.dtype(obj)))
    if dataclasses.is_dataclass(obj) and not isinstance(obj, type):
        return (type(obj).__module__, type(obj).__qualname__) + tuple(
            (f.name, sub(getattr(obj, f.name)))
            for f in dataclasses.fields(obj))
    if isinstance(obj, functools.partial):
        return ("partial", sub(obj.func), sub(obj.args), sub(obj.keywords))
    if isinstance(obj, types.MethodType):
        return ("method", sub(obj.__func__), sub(obj.__self__))
    if isinstance(obj, (types.BuiltinFunctionType, type, types.ModuleType)):
        return _named(obj)
    if type(obj).__name__ == "Mesh" and hasattr(obj, "devices"):
        # its repr names the axes and their sizes, not which devices
        return ("mesh", tuple(obj.shape.items()),
                tuple(int(d.id) for d in obj.devices.flat))
    if isinstance(obj, types.FunctionType):
        if not _text_held(obj.__module__):
            raise Unkeyable(f"{obj.__module__}.{obj.__qualname__}: a "
                            f"function whose text no digest holds")
        # its text is the source digest's or its distribution's version's
        # (the line tells two lambdas of one scope apart); what it HOLDS is
        # neither:
        # a loss function closes over its hyper-parameters, an optimizer's
        # update over its rates, and both bake them into the program
        top = sys.modules.get(obj.__module__.split(".")[0])
        return _named(obj) + (
            getattr(top, "__version__", None), obj.__code__.co_firstlineno,
            sub(obj.__defaults__), sub(obj.__kwdefaults__),
            tuple(sub(c.cell_contents) for c in obj.__closure__ or ()),
        )
    text = repr(obj)
    if " at 0x" in text or " object at " in text:
        raise Unkeyable(f"{type(obj).__qualname__} has no stable text")
    return (type(obj).__module__, type(obj).__qualname__, text)


def _named(obj: Any) -> tuple:
    return ("named", getattr(obj, "__module__", None),
            getattr(obj, "__qualname__", getattr(obj, "__name__", None)))


def _text_held(module: Optional[str]) -> bool:
    """Whether some part of the key holds the TEXT of this module's
    functions: this package's is in the source digest; a built-in's, or one
    from under the interpreter's library paths, is its distribution's
    version's (keyed beside the function's name). A script's or a test's
    is held by nothing."""
    if module in (None, "__main__"):
        return False
    if module.split(".")[0] == "areal_tpu":
        return True
    path = getattr(sys.modules.get(module), "__file__", None)
    if path is None:        # a built-in
        return True
    path = os.path.abspath(path)
    paths = sysconfig.get_paths()
    return any(path.startswith(os.path.abspath(paths[p]) + os.sep)
               for p in ("stdlib", "purelib", "platlib"))


def fingerprint(obj: Any) -> str:
    """A digest of ``obj`` that two processes agree on when they built it
    from the same source and settings: dataclasses field by field, arrays
    by their bytes, a function of this package or of the installation by
    its name and what its closure and defaults hold. Raises
    :class:`Unkeyable` where something in it has no stable text, or is a
    function from elsewhere."""
    return hashlib.sha256(repr(_stable(obj)).encode()).hexdigest()


@functools.lru_cache(maxsize=None)
def source_digest() -> str:
    """A digest of the package's source, ``areal_tpu/**/*.py`` by relative
    path and content; once a process."""
    root = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    h = hashlib.sha256()
    for folder, dirs, files in os.walk(root):
        dirs[:] = sorted(d for d in dirs if d != "__pycache__")
        for name in sorted(files):
            if name.endswith(".py"):
                path = os.path.join(folder, name)
                h.update(os.path.relpath(path, root).encode() + b"\0")
                with open(path, "rb") as f:
                    h.update(f.read())
                h.update(b"\0")
    return h.hexdigest()


@functools.lru_cache(maxsize=None)
def _platform() -> tuple:
    import jax
    import jaxlib

    devs = jax.devices()
    return (
        jax.__version__, jaxlib.__version__,
        devs[0].platform, devs[0].device_kind,
        getattr(devs[0].client, "platform_version", ""),
        # which chips these are: ids restart at 0 in every process of a
        # host whose chips are split between processes
        tuple((d.id, getattr(d, "coords", None)) for d in devs),
        jax.local_device_count(), jax.process_index(), jax.process_count(),
    )


def _jax_settings() -> tuple:
    """Every ``jax.config`` value, set by a ``JAX_*`` variable or in code
    (x64, a matmul precision, the PRNG, the partitioner), but the cache's
    own (where it lives, how large, what it keeps) and ``jax_platforms``
    (the platform found is in the key)."""
    import jax

    return tuple(sorted(
        (k, repr(v)) for k, v in jax.config.values.items()
        if "cache" not in k and k != "jax_platforms"))


def _leaf_signature(x: Any) -> tuple:
    import jax

    if isinstance(x, jax.Array):
        sh = x.sharding
        return (x.shape, str(x.dtype), bool(getattr(x, "weak_type", False)),
                repr(sh), tuple(d.id for d in sh._device_assignment),
                bool(x._committed))
    if isinstance(x, (np.ndarray, np.generic)):
        return (x.shape, str(x.dtype), False, "host")
    if isinstance(x, (bool, int, float, complex)):
        return ((), type(x).__name__, True, "host")
    raise Unkeyable(f"an argument of type {type(x).__qualname__}")


def signature(args: tuple) -> tuple:
    """What a call's arguments look like to the compiler: their tree, and
    of every leaf the shape, dtype, weak type, sharding (with its devices)
    and whether it is committed there."""
    import jax

    leaves, tree = jax.tree.flatten(args)
    return (str(tree),) + tuple(_leaf_signature(x) for x in leaves)


def program_key(name: str, static: Any, built_from: Any, jit_kw: Dict,
                sig: tuple) -> str:
    parts = (
        name, _stable(static), _stable(built_from), _stable(jit_kw), sig,
        source_digest(), _platform(), _jax_settings(),
        constants.program_env(),
    )
    return hashlib.sha256(repr(parts).encode()).hexdigest()


# --------------------------------------------------------------------- #
# Files
# --------------------------------------------------------------------- #


def _path(key: str) -> str:
    return os.path.join(_dir, key + SUFFIX)


def _read(key: str) -> Optional[dict]:
    """The entry under ``key``, or None: missing, truncated, foreign, of
    another key. Marks it used (the eviction's clock is the file's mtime)."""
    path = _path(key)
    try:
        with open(path, "rb") as f:
            blob = f.read()
        if not blob.startswith(_MAGIC):
            return None
        entry = pickle.loads(_unpack(blob[len(_MAGIC):]))
        if entry["key"] != key:
            return None
        os.utime(path)
        return entry
    except FileNotFoundError:
        return None
    except Exception as e:     # unreadable is a miss, and is written again
        logger.warning("program store: %s is unreadable (%r)", path, e)
        return None


def _pack(raw: bytes) -> bytes:
    """Compressed as JAX's own cache compresses where zstandard is
    installed; raw where it is not (an entry of the other kind does not
    unpickle there: a miss, written again)."""
    return raw if zstandard is None else (
        zstandard.ZstdCompressor(level=3).compress(raw))


def _unpack(blob: bytes) -> bytes:
    return blob if zstandard is None else (
        zstandard.ZstdDecompressor().decompress(blob))


def _write(key: str, entry: dict) -> None:
    """Atomically: a temporary name in the same directory, then rename;
    nothing where the entry is larger than the bound allows."""
    blob = _MAGIC + _pack(pickle.dumps(entry, protocol=4))
    bound = max_bytes()
    if bound == 0 or 0 < bound < len(blob):
        return
    os.makedirs(_dir, exist_ok=True)
    tmp = os.path.join(_dir, f".tmp-{os.getpid()}-{threading.get_ident()}-{key[:16]}")
    with open(tmp, "wb") as f:
        f.write(blob)
    os.replace(tmp, _path(key))
    evict(bound)


def _entries() -> list:
    """``(mtime_ns, name, bytes)`` of every entry, oldest use first."""
    found = []
    try:
        names = os.listdir(_dir)
    except FileNotFoundError:
        return found
    for name in names:
        if name.endswith(SUFFIX):
            try:
                st = os.stat(os.path.join(_dir, name))
            except OSError:     # another process evicted it
                continue
            found.append((st.st_mtime_ns, name, st.st_size))
    return sorted(found)


def evict(bound: int) -> None:
    """Least recently used out until the directory is within ``bound``
    bytes (negative: no bound)."""
    entries = _entries()
    total = sum(size for _, _, size in entries)
    for _, name, size in entries:
        if bound < 0 or total <= bound:
            break
        try:
            os.unlink(os.path.join(_dir, name))
        except OSError:
            pass
        total -= size


class _Writer(threading.Thread):
    """ONE thread a process serialises what the start built and writes it,
    behind the start: ``flush`` (also at exit) waits for what is queued."""

    def __init__(self):
        super().__init__(name="program-store-writer", daemon=True)
        self.q: "queue.Queue" = queue.Queue()
        atexit.register(self.flush)

    def run(self):
        while True:
            key, name, compiled = self.q.get()
            try:
                _store(key, name, compiled)
            except Exception as e:     # a program that does not serialise
                logger.info("program store: %s not stored (%r)", name, e)
            finally:
                self.q.task_done()

    def flush(self, timeout: float = 120.0) -> None:
        t_end = time.monotonic() + timeout
        while self.q.unfinished_tasks and time.monotonic() < t_end:
            time.sleep(0.01)


def _store(key: str, name: str, compiled) -> None:
    from jax.experimental import serialize_executable

    payload, _, out_tree = serialize_executable.serialize(compiled)
    devices = [d.id for d in compiled.runtime_executable().local_devices()]
    _write(key, {"key": key, "name": name, "payload": payload,
                 "out_tree": out_tree, "devices": devices})


def _enqueue(key: str, name: str, compiled) -> None:
    global _writer
    with _lock:
        if _writer is None:
            _writer = _Writer()
            _writer.start()
    _writer.q.put((key, name, compiled))


def flush() -> None:
    """Wait until what this process built so far is in the store."""
    if _writer is not None:
        _writer.flush()


# --------------------------------------------------------------------- #
# The wrapper
# --------------------------------------------------------------------- #


def _reserialises() -> bool:
    """Whether an executable that JAX's persistent cache handed over (a
    start with a warm cache and no entry here) serialises again to what it
    was loaded from. XLA:CPU's does not: it comes back without its kernels
    (``NOT_FOUND: Function wrapped_reduce`` at the first call, jax 0.9.0),
    so there such a program waits for a start that compiles it."""
    import jax

    return jax.default_backend() != "cpu"


def _storable_world() -> bool:
    import jax

    return jax.process_count() == 1


class StoredProgram:
    """Called exactly as ``jax.jit(fn, **jit_kw)`` is; every other
    attribute (``lower``, ``trace``, ``eval_shape``) is the jitted
    function's own."""

    def __init__(self, fn: Callable, name: str, static: Any,
                 built_from: Any, jit_kw: Dict):
        import jax

        tracing.listen_for_compiles()    # a miss is booked by JAX's events
        self._jitted = jax.jit(fn, **jit_kw)
        self._name, self._static = name, static
        self._built_from, self._jit_kw = built_from, jit_kw
        self._fun_name = f"jit({getattr(fn, '__name__', name)})"
        self._runs: Dict[tuple, Callable] = {}     # signature -> what runs it
        self._last: Optional[Callable] = None
        self.__wrapped__ = fn

    def __getattr__(self, attr):
        if attr == "_jitted":       # a copy in the making: not yet set
            raise AttributeError(attr)
        return getattr(self._jitted, attr)

    def _cache_size(self) -> int:
        """Specialisations, as ``PjitFunction._cache_size`` counts them:
        one a loaded or built executable, and the jitted function's own
        (calls that stayed on it; an executable built by ``lower`` and
        ``compile`` is none of those)."""
        return jitcache.cache_size(self._jitted) + sum(
            run is not self._jitted for run in self._runs.values())

    def __call__(self, *args):
        run = self._last
        if run is not None:
            try:
                return run(*args)
            except (TypeError, ValueError):
                # not the arguments it was built for (refused before
                # anything ran or was donated): another specialisation,
                # or the caller's own mistake, which the call below raises
                pass
        run = self._last = self._run_for(args)
        return run(*args)

    def _run_for(self, args: tuple) -> Callable:
        """What runs a call of these arguments: the executable loaded or
        built for their signature, or the jitted function itself where the
        program cannot be keyed or stored."""
        try:
            if not _storable_world():
                raise Unkeyable("a multi-process world")
            if constants.dumps_ir():
                raise Unkeyable("the process dumps every program's lowering")
            sig = signature(args)
            run = self._runs.get(sig)
            if run is None:
                key = program_key(self._name, self._static, self._built_from,
                                  self._jit_kw, sig)
                run = self._runs[sig] = self._build(key, args)
        except Unkeyable as e:
            logger.debug("program store: %s stays on jit (%s)", self._name, e)
            run = self._runs[None] = self._jitted
        return run

    def _build(self, key: str, args: tuple) -> Callable:
        loaded = self._load(key, args)
        if loaded is not None:
            return loaded
        tracing.program_missed(self._fun_name)
        compiled = self._jitted.lower(*args).compile()
        if not tracing.missed_from_cache() or _reserialises():
            _enqueue(key, self._name, compiled)
        return compiled

    def _load(self, key: str, args: tuple):
        import jax
        from jax.experimental import serialize_executable

        t0 = time.perf_counter()
        entry = _read(key)
        if entry is None:
            return None
        try:
            by_id = {d.id: d for d in jax.devices()}
            compiled = serialize_executable.deserialize_and_load(
                entry["payload"], jax.tree.structure((args, {})),
                entry["out_tree"],
                execution_devices=[by_id[i] for i in entry["devices"]])
        except Exception as e:     # fails to load: a miss, written again
            logger.warning("program store: %s did not load (%r)", self._name, e)
            return None
        tracing.program_loaded(self._fun_name, time.perf_counter() - t0)
        return compiled


def stored_jit(fn: Callable, *, name: str, key: Any = (),
               built_from: Any = (), **jit_kw) -> Callable:
    """``jax.jit(fn, **jit_kw)``, through the store where one is open.
    ``name`` and ``key`` say which program of the caller's this is (its
    dictionary's name and static key); ``built_from`` is everything the
    closure reads that the arguments do not show."""
    import jax

    if _dir is None:
        return jax.jit(fn, **jit_kw)
    return StoredProgram(fn, name, key, built_from, jit_kw)
