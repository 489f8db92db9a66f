"""Jax jit-cache introspection (one guarded home for a private API).

``PjitFunction._cache_size`` counts jax-level specializations — the signal
the benchmark's drivers use to see that a window held no compile
(re-specializations from sharding/layout drift that python-level compile
counters cannot see). It is private to jax, so both engines go through this
helper: an upgrade that removes it degrades the gate to 0 instead of
crashing a run mid-benchmark.
"""

from typing import Any, Iterable


def cache_size(jitted: Any) -> int:
    fn = getattr(jitted, "_cache_size", None)
    return int(fn()) if callable(fn) else 0


def total_cache_size(jitted_fns: Iterable[Any]) -> int:
    return sum(cache_size(j) for j in jitted_fns)
