"""Tier-1 static async-hygiene pass (``tools.arealint``'s four legacy
async rules, ``LEGACY_ASYNC_RULES``).

Keeps ``areal_tpu/system/`` and ``areal_tpu/train/`` free of the bug
classes the fault-tolerance subsystems fixed: bare ``asyncio.gather(``
without ``return_exceptions`` (one dead peer aborts the whole fan-out),
discarded ``create_task`` results (unreferenced tasks can be GC'd; their
exceptions vanish), ``shutil.rmtree`` on checkpoint-capable paths outside
the commit helper (a crash mid-save destroys the only restore point), and
``time.sleep`` inside ``async def`` (blocks the event loop).
"""

import functools
import os
import textwrap

from tools import arealint

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))

scan_source = functools.partial(
    arealint.scan_source, rules=arealint.LEGACY_ASYNC_RULES
)
scan_paths = functools.partial(
    arealint.scan_paths, rules=arealint.LEGACY_ASYNC_RULES
)


def test_system_layer_is_clean():
    findings = scan_paths([
        os.path.join(REPO, "areal_tpu", "system"),
        os.path.join(REPO, "areal_tpu", "train"),
    ])
    assert findings == [], "\n".join(str(f) for f in findings)


def test_checker_flags_bare_gather_and_discarded_task():
    src = textwrap.dedent(
        """
        import asyncio

        async def bad():
            await asyncio.gather(one(), two())
            asyncio.get_event_loop().create_task(three())

        async def good():
            await asyncio.gather(one(), two(), return_exceptions=True)
            t = asyncio.get_event_loop().create_task(three())
            await t
        """
    )
    rules = sorted(f.rule for f in scan_source(src))
    assert rules == ["bare-gather", "discarded-task"]


def test_checker_suppression_and_non_asyncio_gather():
    src = textwrap.dedent(
        """
        import asyncio

        async def deliberate():
            await asyncio.gather(one(), two())  # async-hygiene: ok

        def data_join(batch):
            return SequenceSample.gather(batch)  # not asyncio: ignored
        """
    )
    assert scan_source(src) == []


def test_checker_flags_live_checkpoint_rmtree():
    src = textwrap.dedent(
        """
        import shutil
        from shutil import rmtree

        def clean(path):
            shutil.rmtree(path)
            rmtree(path)
            shutil.rmtree(path)  # async-hygiene: ok
        """
    )
    rules = [f.rule for f in scan_source(src, "areal_tpu/train/x.py")]
    assert rules == ["live-checkpoint-rmtree", "live-checkpoint-rmtree"]
    # the commit helper itself is the one sanctioned deletion site
    assert scan_source(src, "areal_tpu/base/recover.py") == []


def test_checker_flags_time_sleep_in_async():
    src = textwrap.dedent(
        """
        import asyncio
        import time

        async def bad():
            time.sleep(1.0)
            if True:
                time.sleep(2.0)

        async def bad_from_import():
            from time import sleep
            sleep(3.0)

        async def fine():
            await asyncio.sleep(1.0)
            time.sleep(0.1)  # async-hygiene: ok

            def sync_helper():
                time.sleep(0.5)  # runs where called (executor thread): ok

        async def fine_awaited_bare():
            from asyncio import sleep
            await sleep(1.0)  # asyncio's sleep via from-import: awaited

        def also_fine():
            time.sleep(1.0)
        """
    )
    findings = [f for f in scan_source(src) if f.rule == "sleep-in-async"]
    assert len(findings) == 3
    assert all("blocks the event loop" in f.message for f in findings)
