"""Tier 1 collects ``tests/`` only: this file brings in the 24 cases of
``benchmark/tests/test_resident.py`` as they stand (the resident count of
``benchmark/resident.py`` and the readers that divide by it; PERF.md §7,
"Open after PR 49" (1b)). They guard the count that
``gen.kv_read_once_share`` (the program's side) is read beside
(``gen.kv_shared_share``, the driver's)."""

import pytest

pytest.register_assert_rewrite("benchmark.tests.test_resident")

from benchmark.tests.test_resident import *  # noqa: E402,F401,F403
