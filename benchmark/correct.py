"""The comparison that decides ``correct``.

Log-probabilities the system produced (served by the engine, or computed
by the trainer's inference pass) are held to the plain float32 reference on
the same token sequences. The tolerance is chip_smoke.py's rule (PR 21):
twice what the served dtype alone costs the plain reference on the same
sequences (max abs difference of its bf16 and float32 runs), plus a floor
of 0.02 nats. A path that computes in lower precision than the
configuration states, drops a term or reads the wrong position lands far
outside: with these random weights a wrong context moves a log-probability
by about a nat.
"""

import importlib
from typing import Dict, Sequence

import numpy as np

FLOOR_NATS = 0.02


def reference_module(name: str):
    return importlib.import_module(f"benchmark.reference.{name}")


def check_logprobs(
    params, arch: dict, served_dtype: str,
    samples: Sequence[Dict],
) -> Dict:
    """``samples``: dicts with ``tokens`` (whole sequence), ``start`` (index
    of the first token whose log-probability the system reported) and
    ``logprobs`` (the system's values for tokens[start:]). Returns the
    verdict with the numbers behind it."""
    ref = reference_module(arch["reference"])
    if not samples:
        return {"correct": False, "reason": "no sample to compare"}
    pad = max(len(s["tokens"]) for s in samples)
    pad = -(-pad // 256) * 256
    yard, diff, mean_parts, n = 0.0, 0.0, 0.0, 0
    for s in samples:
        toks, start = s["tokens"], s["start"]
        got = np.asarray(s["logprobs"], np.float64)
        f32, _ = ref.next_token_logprobs(params, arch, toks, "float32", pad)
        low, _ = ref.next_token_logprobs(params, arch, toks, served_dtype, pad)
        f32, low = f32[start - 1:], low[start - 1:]
        if len(got) != len(f32) or not np.isfinite(got).all():
            return {"correct": False,
                    "reason": f"{len(got)} values for {len(f32)} positions, "
                              f"or a non-finite one"}
        yard = max(yard, float(np.abs(low - f32).max()))
        d = np.abs(got - f32)
        diff = max(diff, float(d.max()))
        mean_parts += float(d.sum())
        n += len(d)
    tol = 2 * yard + FLOOR_NATS
    return {
        "correct": bool(diff <= tol),
        "max_abs_diff_nats": diff,
        "mean_abs_diff_nats": mean_parts / max(n, 1),
        "reference_served_dtype_vs_f32_nats": yard,
        "tolerance_nats": tol,
        "n_sequences": len(samples),
        "n_positions": n,
    }
