"""Profiling experiment: timed train steps on synthetic data.

Counterpart of the reference's null/profile experiment
(``realhf/experiments/common/null_exp.py`` + ``training/main_profile.py``):
run N timed SFT steps of a given model/parallelism on synthetic packed
batches, print per-step wall time and achieved TFLOP/s as one JSON line.
Combine with ``AREAL_DUMP_TRACE=1`` to capture ``jax.profiler`` traces of
exactly these steps (``base/tracing.py``).
"""

import argparse
import dataclasses
import json
import logging
import sys
import time
from typing import List

logger = logging.getLogger("areal_tpu.profile")


def run_profile(
    model_spec,
    seqlens: List[int],
    n_steps: int = 8,
    n_warmup: int = 2,  # >= 1: the first step compiles
    n_mbs: int = 1,
    seed: int = 0,
) -> dict:
    """``mfu`` is reported only on a device with a row in
    ``flops.DEVICE_PEAKS`` (the CLI insists on one; a library caller on
    any other device gets step time and TFLOP/s, and no utilization)."""
    import numpy as np

    import jax

    from areal_tpu.api.data import MicroBatchSpec, SequenceSample
    from areal_tpu.base import flops as flops_mod
    from areal_tpu.base.tracing import maybe_trace
    from areal_tpu.interfaces.sft import sft_loss_fn
    from areal_tpu.train.engine import TrainEngine

    cfg = model_spec.model_config()
    eng = TrainEngine(
        cfg, model_spec.parallel_config(), model_spec.optimizer
    )
    eng.init_random(seed)
    eng.setup_optimizer(total_train_steps=max(n_steps * 10, 100))

    T = sum(seqlens)
    rng = np.random.default_rng(seed)
    sample = SequenceSample.from_default(
        ids=list(range(len(seqlens))),
        seqlens=list(seqlens),
        data={
            "packed_input_ids": rng.integers(0, cfg.vocab_size, T).astype(
                np.int64
            ),
            "prompt_mask": np.zeros(T, bool),
        },
    )
    spec = MicroBatchSpec(n_mbs=n_mbs, max_tokens_per_mb=T)

    if n_steps < 1:
        raise ValueError(f"n_steps must be >= 1, got {n_steps}")
    for _ in range(max(n_warmup, 1)):  # at least one: the first step compiles
        stats = eng.train_batch(sample, spec, sft_loss_fn, fetch_stats=False)
    jax.device_get(stats["loss"])

    with maybe_trace("profile"):
        t0 = time.perf_counter()
        for _ in range(n_steps):
            stats = eng.train_batch(
                sample, spec, sft_loss_fn, fetch_stats=False
            )
        jax.device_get(stats["loss"])
        dt = (time.perf_counter() - t0) / n_steps

    fl = flops_mod.train_flops(cfg, T, seqlens=seqlens)
    dev = jax.devices()[0]
    peaks = flops_mod.DEVICE_PEAKS.get(dev.device_kind)
    return {
        "metric": "profile_step",
        "device": {
            "platform": dev.platform, "kind": dev.device_kind,
            "count": len(jax.devices()),
        },
        "step_time_s": round(dt, 5),
        "tokens_per_s": round(T / dt, 1),
        "tflops_per_s": round(fl / dt / 1e12, 2),
        **(
            {"mfu": round(fl / dt / peaks.bf16_flops / len(jax.devices()), 4)}
            if peaks else {}
        ),
        "n_params": int(flops_mod.param_count(cfg)),
        "seqlens": list(seqlens),
        "n_steps": n_steps,
    }


def main(argv=None):
    logging.basicConfig(level=logging.INFO)
    ap = argparse.ArgumentParser(prog="areal_tpu profile")
    ap.add_argument("--config", default=None, help="YAML with a ModelSpec")
    ap.add_argument("--seqlens", default="512x8",
                    help="'LENxN' or comma list, e.g. 512x8 or 8192")
    ap.add_argument("--n-steps", type=int, default=8)
    ap.add_argument("--n-mbs", type=int, default=1)
    ap.add_argument("overrides", nargs="*")
    args = ap.parse_args(argv)

    from areal_tpu.base import compile_cache

    compile_cache.configure()
    import jax

    from areal_tpu.base import flops as flops_mod
    from areal_tpu.experiments.config import ModelSpec
    from areal_tpu.experiments import load_config

    # utilization needs a published peak: fail before the timed steps
    flops_mod.device_peaks(jax.devices()[0].device_kind)
    spec = load_config(ModelSpec, args.config, args.overrides)
    if "x" in args.seqlens:
        ln, n = args.seqlens.split("x")
        seqlens = [int(ln)] * int(n)
    else:
        seqlens = [int(x) for x in args.seqlens.split(",")]
    out = run_profile(
        spec, seqlens, n_steps=args.n_steps, n_mbs=args.n_mbs,
    )
    print(json.dumps(out))
    return 0


if __name__ == "__main__":
    sys.exit(main())
