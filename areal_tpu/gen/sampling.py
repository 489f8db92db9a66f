"""Logits warping + token sampling.

Counterpart of ``realhf/impl/model/utils/logits_warper.py`` (225 LoC) and the
sampling half of ``genstep`` (``real_llm_generate.py:30``): temperature,
top-k, top-p, greedy — vectorized over a slot batch, jit-friendly (no
data-dependent shapes; top-p uses sort + cumulative mass masking).
"""

import dataclasses
from typing import Optional, Tuple

import jax
import jax.numpy as jnp

NEG_INF = -1e10


@jax.tree_util.register_dataclass
@dataclasses.dataclass
class SamplingParams:
    """Per-slot sampling hyperparameters (device arrays, [B])."""

    temperature: jnp.ndarray   # f32; 0 => greedy
    top_p: jnp.ndarray         # f32 in (0, 1]
    top_k: jnp.ndarray         # i32; >= vocab => disabled

    @classmethod
    def filled(cls, batch: int, temperature=1.0, top_p=1.0, top_k=1 << 30):
        return cls(
            temperature=jnp.full((batch,), temperature, jnp.float32),
            top_p=jnp.full((batch,), top_p, jnp.float32),
            top_k=jnp.full((batch,), top_k, jnp.int32),
        )


def warp_logits(logits: jnp.ndarray, sp: SamplingParams) -> jnp.ndarray:
    """[B, V] -> warped [B, V] (fp32). Greedy slots (temperature 0) pass
    through — the sampler handles them with argmax.

    ONE descending sort serves both warpers (a [B, V] sort at a 152k vocab
    is the dominant cost of a decode step — the original
    sort-per-warper formulation was 3 sorts): top-k masks the sorted TAIL
    (suffix positions >= k), top-p thresholds the cumulative mass over the
    same masked sorted array, and both come back to the unsorted layout as
    VALUE comparisons — which also preserves keep-ties-at-the-threshold
    semantics."""
    logits = logits.astype(jnp.float32)
    B, V = logits.shape
    temp = jnp.maximum(sp.temperature, 1e-6)[:, None]
    logits = logits / temp

    sorted_desc = jnp.sort(logits, axis=-1)[:, ::-1]
    # top-k in sorted space: mask suffix positions
    pos = jnp.arange(V)[None, :]
    masked_desc = jnp.where(pos < sp.top_k[:, None], sorted_desc, NEG_INF)
    # top-p over the top-k-masked distribution (still sorted descending)
    probs_desc = jax.nn.softmax(masked_desc, axis=-1)
    cum = jnp.cumsum(probs_desc, axis=-1)
    keep_desc = ((cum - probs_desc) < sp.top_p[:, None]) & (
        pos < sp.top_k[:, None]
    )
    # threshold value: smallest logit still kept (first token always kept)
    n_keep = jnp.maximum(keep_desc.sum(-1), 1)
    thresh = jnp.take_along_axis(sorted_desc, (n_keep - 1)[:, None], axis=-1)
    return jnp.where(logits < thresh, NEG_INF, logits)


def _plain_temperature(logits: jnp.ndarray, sp: SamplingParams) -> jnp.ndarray:
    """The no-warp arm of sampling: f32 logits over temperature (floored),
    broadcast over any number of trailing-position axes before the vocab."""
    temp = jnp.maximum(sp.temperature, 1e-6).reshape(
        sp.temperature.shape + (1,) * (logits.ndim - 1)
    )
    return logits.astype(jnp.float32) / temp


def warp_logits_rows(
    logits: jnp.ndarray, sp: SamplingParams, rows: jnp.ndarray
) -> jnp.ndarray:
    """Warp ONLY the slots named by ``rows`` (host-known warping-slot
    indices, padded with an out-of-range index): the sort — the dominant
    cost of a decode step at a 152k vocab — runs over ``[W, V]`` (or
    ``[W*C, V]`` for ``[B, C, V]`` logits) where W is the
    warping-slot bucket, never the whole batch; every other slot gets the
    plain temperature scaling of the ``warp=False`` path. Exactly
    equivalent per row to full-batch :func:`warp_logits` /
    :func:`warp_logits_multi` — a greedy slot's result is identical either
    way (temperature 0 passes warping through), so mixed batches stay
    correct while greedy traffic stops paying for one top-p request."""
    B = logits.shape[0]
    safe = jnp.clip(rows, 0, B - 1)
    sub_sp = SamplingParams(
        temperature=sp.temperature[safe],
        top_p=sp.top_p[safe],
        top_k=sp.top_k[safe],
    )
    sub = logits[safe]
    if logits.ndim == 3:
        warped_rows = warp_logits_multi(sub, sub_sp)
    else:
        warped_rows = warp_logits(sub, sub_sp)
    # padding indices (== B) drop; a clipped duplicate of row B-1 in the
    # gather is then never scattered back
    return _plain_temperature(logits, sp).at[rows].set(
        warped_rows, mode="drop"
    )


def warp_logits_multi(logits: jnp.ndarray, sp: SamplingParams) -> jnp.ndarray:
    """Warp ``[B, C, V]`` logits (C query positions per slot) with per-SLOT
    sampling params. ONE ``[B*C, V]`` sort serves every position of every
    slot."""
    B, C, V = logits.shape
    flat_sp = SamplingParams(
        temperature=jnp.repeat(sp.temperature, C),
        top_p=jnp.repeat(sp.top_p, C),
        top_k=jnp.repeat(sp.top_k, C),
    )
    return warp_logits(logits.reshape(B * C, V), flat_sp).reshape(B, C, V)


def sample_tokens(
    rng: jax.Array,
    logits: jnp.ndarray,
    sp: SamplingParams,
    greedy: Optional[jnp.ndarray] = None,
    warp: bool = True,
    warp_rows: Optional[jnp.ndarray] = None,
) -> Tuple[jnp.ndarray, jnp.ndarray]:
    """Sample one token per slot. Returns (tokens [B] i32, logprobs [B] f32).

    ``logprobs`` are w.r.t. the *warped* distribution (matching SGLang's
    returned logprobs under sampling parameters).

    ``warp=False`` (STATIC) skips the top-k/top-p warp entirely — pure
    temperature sampling needs no ``[B, V]`` sort, and the sort is the
    single most expensive op of a decode step at a 152k vocab. Callers that
    know no request warps (the engine tracks this host-side) pass False.
    ``warp_rows`` (with ``warp=True``) narrows the sort to the named slots
    only (:func:`warp_logits_rows`) — mixed batches pay for their warping
    requests, not for the batch. The result is EXACT in every mode.
    """
    if not warp:
        # no-warp fast path: only the SAMPLED token's logprob is reported,
        # so gather-then-normalize (logp[t] = warped[t] - logsumexp) skips
        # the full [B, V] log_softmax materialization — same math as
        # jax.nn.log_softmax at the gathered index, exactness pinned by
        # tests/test_fused_sample.py
        warped = _plain_temperature(logits, sp)
        sampled = jax.random.categorical(rng, warped, axis=-1)
        arg = jnp.argmax(logits, axis=-1)
        if greedy is None:
            greedy = sp.temperature <= 0.0
        tokens = jnp.where(greedy, arg, sampled).astype(jnp.int32)
        gathered = jnp.take_along_axis(warped, tokens[:, None], axis=-1)
        lp = (
            gathered - jax.scipy.special.logsumexp(
                warped, axis=-1, keepdims=True
            )
        )[:, 0]
        return tokens, lp
    if warp_rows is not None:
        warped = warp_logits_rows(logits, sp, warp_rows)
    else:
        warped = warp_logits(logits, sp)
    logp = jax.nn.log_softmax(warped, axis=-1)
    sampled = jax.random.categorical(rng, warped, axis=-1)
    arg = jnp.argmax(logits, axis=-1)
    if greedy is None:
        greedy = sp.temperature <= 0.0
    tokens = jnp.where(greedy, arg, sampled).astype(jnp.int32)
    lp = jnp.take_along_axis(logp, tokens[:, None], axis=-1)[:, 0]
    return tokens, lp
