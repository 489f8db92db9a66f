"""TPU HBM observability + pressure action.

TPU-native counterpart of the reference's per-step GPU memory monitoring
(``realhf/system/model_worker.py:1507-1610``: pynvml used/total gather +
``REAL_GPU_MEMORY_KILL_THRESHOLD`` kill switch). On TPU the source is the
PJRT device's ``memory_stats()`` (bytes_in_use / peak_bytes_in_use /
bytes_limit); platforms that don't report (CPU tests) degrade to no-op.

Two thresholds, both fractions of ``bytes_limit``:
- warn (``AREAL_HBM_WARN_THRESHOLD``, default 0.92): log once per crossing.
- kill (``AREAL_HBM_KILL_THRESHOLD``, default 1.0 = disabled): raise
  :class:`HBMPressureError` so the worker dies loudly and the launcher's
  restart-the-world recovery takes over — the reference's exact semantics
  (a worker past the threshold raises RuntimeError, model_worker.py:1512).

On 16 GiB v5e chips serving a 7B model with a 12.5 GB/chip budget
(examples/qwen2_5_7b_async_v5e.yaml), creeping page-pool or compile-buffer
growth OOMs the pod with no warning otherwise.
"""

import logging
from typing import Dict, Optional

from areal_tpu.base import constants

logger = logging.getLogger("areal_tpu.hbm")

class HBMPressureError(RuntimeError):
    """Device memory exceeded the kill threshold."""


def device_memory_stats(device=None) -> Optional[Dict[str, int]]:
    """Normalized snapshot ``{bytes_in_use, peak_bytes_in_use, bytes_limit}``
    for one device, or None when the platform doesn't report (the CPU;
    an attached TPU reports — chip_smoke.py fails if it does not)."""
    if device is None:
        import jax

        device = jax.devices()[0]
    try:
        raw = device.memory_stats()
    except Exception:  # noqa: BLE001 — platform without memory stats
        return None
    if not raw or "bytes_in_use" not in raw:
        return None
    return {
        "bytes_in_use": int(raw["bytes_in_use"]),
        "peak_bytes_in_use": int(raw.get("peak_bytes_in_use", raw["bytes_in_use"])),
        "bytes_limit": int(raw.get("bytes_limit", 0)),
    }


def live_array_bytes() -> int:
    """Client-side lower bound on device memory: bytes of all live jax
    arrays this process references. Misses compiler temporaries and donated
    aliasing, but works where ``memory_stats()`` doesn't report (the CPU
    test harness) — the gauge that keeps those runs observable."""
    import jax

    return sum(
        x.nbytes for x in jax.live_arrays() if not x.is_deleted()
    )


class HBMMonitor:
    """Per-process monitor: call :meth:`check` once per step/chunk.

    Returns scalar gauges for the caller's stats sink (empty dict when the
    platform doesn't report), warns once per threshold crossing, and raises
    :class:`HBMPressureError` past the kill threshold.
    """

    def __init__(
        self,
        device=None,
        warn_threshold: Optional[float] = None,
        kill_threshold: Optional[float] = None,
        tag: str = "",
    ):
        self._device = device
        self.warn_threshold = (
            constants.hbm_warn_threshold()
            if warn_threshold is None else warn_threshold
        )
        self.kill_threshold = (
            constants.hbm_kill_threshold()
            if kill_threshold is None else kill_threshold
        )
        self.tag = tag
        self._warned = False
        # throttle for the live-array FALLBACK only: jax.live_arrays() walks
        # every array the process references, which is O(all arrays alive) —
        # called per serving-loop iteration / train step it degrades from
        # "cheap gauge" to a real tax as a long-lived process accumulates
        # arrays. It is an observability lower bound, so ~1s staleness is
        # free; the memory_stats() path (TPU) stays unthrottled.
        self.fallback_interval_s = constants.hbm_fallback_interval()
        self._fallback_last_t = 0.0
        self._fallback_cached = 0.0

    def check(self, kill: bool = True) -> Dict[str, float]:
        """Snapshot gauges; warn/kill on thresholds. ``kill=False`` for
        pull-style paths (metrics endpoints) that must never raise."""
        stats = device_memory_stats(self._device)
        if stats is None:
            # platforms without memory_stats (CPU): report the client-side
            # lower bound so dashboards are never fully blind
            import time

            now = time.monotonic()
            if now - self._fallback_last_t >= self.fallback_interval_s:
                self._fallback_last_t = now
                self._fallback_cached = float(live_array_bytes())
            return {"hbm_live_array_bytes": self._fallback_cached}
        limit = stats["bytes_limit"]
        util = stats["bytes_in_use"] / limit if limit else 0.0
        out = {
            "hbm_bytes_in_use": float(stats["bytes_in_use"]),
            "hbm_peak_bytes_in_use": float(stats["peak_bytes_in_use"]),
            "hbm_bytes_limit": float(limit),
            "hbm_util": util,
        }
        if kill and limit and util > self.kill_threshold:
            raise HBMPressureError(
                f"{self.tag or 'device'} HBM {stats['bytes_in_use']/2**30:.2f}"
                f"/{limit/2**30:.2f} GiB = {util:.1%} exceeds kill threshold "
                f"{self.kill_threshold:.2f} (tune ${constants.MEMORY_KILL_ENV})"
            )
        if limit and util > self.warn_threshold:
            if not self._warned:
                logger.warning(
                    "%s HBM pressure: %.2f/%.2f GiB (%.1f%%) past warn "
                    "threshold %.2f ($%s)",
                    self.tag or "device", stats["bytes_in_use"] / 2**30,
                    limit / 2**30, util * 100, self.warn_threshold,
                    constants.MEMORY_WARN_ENV,
                )
                self._warned = True
        else:
            self._warned = False
        return out
