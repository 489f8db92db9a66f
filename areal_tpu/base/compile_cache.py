"""The persistent XLA compile cache and the program store: ONE site
configures both.

A WARM start is one that finds its programs BUILT: the engines' programs
come out of the program store (``base/program_store.py``, in
``<cache>/programs``), loaded under a key digested from what each was built
from, with no Python trace and no lowering; what the store does not hold
(the first start of a tree, a changed configuration or shape, an evicted
entry) is traced and lowered as before and finds its EXECUTABLE in JAX's
cache if it was ever compiled here, so a cold start of the store is what
a warm start was before it existed, and a start with neither compiles.

Every process entry calls :func:`configure` before its first compile
(``apps/launcher._setup_worker_env``, ``gateway/__main__``,
``apps/profile``, ``benchmark/run.py``, ``chip_smoke.py``'s children). Where
``JAX_COMPILATION_CACHE_DIR`` is set from outside, JAX reads it itself and
this function changes nothing. Where it is not, the cache goes to the one
fixed path ``constants.compile_cache_dir()`` names — by exporting that
same variable, so spawned children and a later ``import jax`` agree on it,
and by updating the config of a JAX that is already imported. Never a
path built from ``tempfile``, a pid or the time: the directory is part of
the cache key, and a cache that moves never hits.

A run held to the CPU (``JAX_PLATFORMS=cpu``: the tests, CPU-designated
workers) caches nothing unless the variable asks for it: its programs are
tiny, and XLA:CPU ties a cached executable to the host's CPU features.
The test harness asks (``tests/conftest.py``): one fresh directory a test
run, inherited by the workers and every subprocess world, removed when the
session ends, so a program that many cases build is compiled once a run.

Importing this module does not import JAX, and :func:`configure` never
initialises a backend — the launcher's JAX-free parent calls it too.
Where JAX is already imported it also starts the process's compile
listener (``tracing.listen_for_compiles``), so what the cache hit and
missed is counted from the first program on; where it is not, the
engines' constructors do. It opens the program store inside the
directory it returns, or closes it where it returns None: a process that
never calls it (the test session) has no store.
"""

import os
import sys
from typing import Optional

from areal_tpu.base import constants, program_store, tracing


def configure() -> Optional[str]:
    """Returns the directory the cache lives in, or None when this run
    caches nothing (held to the CPU, variable unset). The program store
    opens inside it, or is closed with it."""
    path = _configure()
    program_store.open_in(path)
    return path


def _configure() -> Optional[str]:
    tracing.listen_for_compiles()   # nothing where JAX is not imported
    if constants.env_str(constants.COMPILE_CACHE_ENV) is not None:
        return constants.compile_cache_dir()    # JAX reads it itself
    if constants.jax_platforms().startswith("cpu"):
        return None
    path = constants.compile_cache_dir()
    os.environ[constants.COMPILE_CACHE_ENV] = path
    jax = sys.modules.get("jax")
    if jax is not None:
        jax.config.update("jax_compilation_cache_dir", path)
    return path
