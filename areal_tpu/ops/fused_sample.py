"""Fused LM-head + sampling epilogue (docs/performance.md "Fused sampling
epilogue").

A decode step's epilogue in the reference path is: materialize the full
``[B, V]`` logits (``x @ W_head``), then sort / log-softmax / categorical
over them (``gen/sampling.py``). At a 152k vocab the logits tensor and its
descending sort dominate the per-token cost — they are one of the two
residuals between measured decode and the HBM roofline (ROADMAP item 2).

This module streams the head over vocab blocks instead: per block it
computes ``logits_blk = x @ W[:, v0:v1]`` and folds the block into online
per-row state —

- running max ``m`` and rescaled sum-of-exponentials ``l`` (the standard
  online-softmax recurrence, same as ``ops/paged_attention.py``'s extend
  kernel) give the exact log-normalizer ``m + log l``;
- a running raw-logits argmax (value, index) makes greedy slots
  *token-exact* vs ``jnp.argmax`` over the full array (strictly-greater
  updates keep the first maximum, matching ``jnp.argmax`` tie order);
- a running **Gumbel-top-1** argmax over ``warped + G`` (``G`` iid Gumbel,
  derived per block from the PRNG key) IS a categorical sample from
  ``softmax(warped)`` — distribution-exact, no ``[B, V]`` materialization;
- an optional running top-``TOPK_MAX`` (value, index) buffer merged per
  block via ``lax.top_k`` serves top-k slots exactly (for ``k <=
  TOPK_MAX``): the final sample is a cheap ``[R, TOPK_MAX]`` categorical
  over the masked buffer.

The head is taken as the parameter tree stores it (``vocab_rows``,
static): ``[E, V]`` for an untied head, ``[V, E]`` for a tied embedding,
whose block is ``W[v0:v1, :]`` contracted over E — no transposed view, no
copy (``models/transformer.py:head_operand``). ``logits_scale`` (static,
``cfg.logits_scaling``) divides the block's float32 logits before the
soft cap, as ``models/transformer.py:_head`` does.

Top-p slots are NOT handled here — they keep the sorted reference path
via the engine's warp-row bucket machinery (PR 9), so only those rows pay
the ``[W, V]`` sort.

Exactness contract (pinned by tests/test_fused_sample.py): greedy slots
are token-exact and logprob-exact (up to float associativity) vs
``sample_tokens``; temperature and top-k slots are distribution-exact —
same marginal, different RNG stream, so individual draws differ from
``jax.random.categorical``. Top-k keeps *exactly k* tokens; the sorted
reference keeps ties at the k-th value (a measure-zero difference for
continuous logits).

Whether an engine's decode steps end in this pass at all is
:func:`fused_sample_applies`, a rule over what the engine can observe (no
flag). Dispatch mirrors ``ops/paged_attention.py``: ``use_pallas=None``
auto-detects (TPU, no top-k buffer, no mesh); the XLA path is itself
streamed (peak extra memory ``[R, block]``, not ``[R, V]``) and serves
CPU/interpret parity, meshes (GSPMD partitions the block matmuls), and
top-k slots. Explicitly requesting the kernel somewhere it cannot run
raises with the real constraint instead of silently degrading.
"""

from typing import Dict, Optional

import jax
import jax.numpy as jnp

# Matches gen/sampling.py: masked-out entries of a distribution.
NEG_INF = -1e10
# Initializer/mask for online state: below any representable warped logit
# (greedy rows divide by the 1e-6 temperature floor, so real warped values
# reach ~1e8 magnitude; -1e10 would be ambiguous there).
_MASK = -2.3819763e38
# Top-k buffer width: slots with top_k <= TOPK_MAX sample exactly from the
# online buffer; larger top_k falls back to the sorted reference path.
TOPK_MAX = 64


def _platform() -> str:
    return jax.devices()[0].platform


def fused_sample_applies(
    cfg, params, mesh=None, platform: Optional[str] = None
) -> bool:
    """Whether a generation engine's decode steps end in the fused
    head-and-sample kernel or in the materialised ``[B, V]`` logits and
    ``gen/sampling.py``, from what the engine can observe (as
    ``kv_write_kernel_applies`` and ``decode_kernel_applies`` say it of
    their kernels). The kernel serves: ONE TPU device (``pallas_call`` has
    no partitioning rule, so any mesh of several keeps the materialised
    path, which GSPMD partitions); a head STORED in the serving dtype,
    which is the embedding itself where the two are tied (the kernel
    streams either layout as the tree holds it,
    ``models/transformer.py:head_operand``; a head kept in another dtype is
    a lazy cast, which as a ``pallas_call`` operand becomes a copy of the
    whole weight every step); a policy, not a critic (whose head is one
    column); a vocabulary of at least one lane tile. Everything else runs
    exactly the programs it ran before the kernel existed. ``params`` is
    the engine's tree as it serves it (arrays or their shapes);
    ``platform`` defaults to the first device's."""
    if platform is None:
        platform = _platform()
    head = params["embed" if cfg.tied_embedding else "head"]["weight"]
    return (
        platform == "tpu"
        and (mesh is None or mesh.size == 1)
        and not cfg.is_critic
        and head.dtype == jnp.dtype(cfg.dtype)
        and cfg.vocab_size >= 128
    )


def _update_block(
    c: Dict[str, jnp.ndarray],
    logits: jnp.ndarray,           # [R, Bk] f32 (soft cap already applied)
    col0,                          # scalar (may be traced): first column id
    key_blk: jax.Array,
    t: jnp.ndarray,                # [R] f32 temperature (floored)
    kmax: int,
) -> Dict[str, jnp.ndarray]:
    """Fold one vocab block into the online per-row state."""
    Bk = logits.shape[1]
    cols = col0 + jnp.arange(Bk, dtype=jnp.int32)
    warped = logits / t[:, None]
    out = dict(c)

    # online logsumexp of the warped logits
    m_new = jnp.maximum(c["m"], jnp.max(warped, axis=-1))
    out["m"] = m_new
    out["l"] = c["l"] * jnp.exp(c["m"] - m_new) + jnp.sum(
        jnp.exp(warped - m_new[:, None]), axis=-1
    )

    # running raw argmax: strict > keeps the earliest maximum, matching
    # jnp.argmax tie order over the full array
    bi = jnp.argmax(logits, axis=-1).astype(jnp.int32)
    bv = jnp.take_along_axis(logits, bi[:, None], axis=-1)[:, 0]
    upd = bv > c["am_v"]
    out["am_v"] = jnp.where(upd, bv, c["am_v"])
    out["am_i"] = jnp.where(upd, col0 + bi, c["am_i"]).astype(jnp.int32)

    # Gumbel-top-1: argmax over warped + G across all blocks is a
    # categorical draw from softmax(warped) (ties are measure-zero)
    u = jax.random.uniform(
        key_blk, warped.shape, minval=jnp.finfo(jnp.float32).tiny, maxval=1.0
    )
    pert = warped - jnp.log(-jnp.log(u))
    pbi = jnp.argmax(pert, axis=-1)
    pbv = jnp.take_along_axis(pert, pbi[:, None], axis=-1)[:, 0]
    pwv = jnp.take_along_axis(warped, pbi[:, None], axis=-1)[:, 0]
    upd2 = pbv > c["g_p"]
    out["g_p"] = jnp.where(upd2, pbv, c["g_p"])
    out["g_w"] = jnp.where(upd2, pwv, c["g_w"])
    out["g_i"] = jnp.where(
        upd2, (col0 + pbi).astype(jnp.int32), c["g_i"]
    ).astype(jnp.int32)

    if "topv" in c:
        cat_v = jnp.concatenate([c["topv"], warped], axis=-1)
        cat_i = jnp.concatenate(
            [c["topi"], jnp.broadcast_to(cols, warped.shape)], axis=-1
        )
        tv, sel = jax.lax.top_k(cat_v, kmax)
        out["topv"] = tv
        out["topi"] = jnp.take_along_axis(cat_i, sel, axis=-1)
    return out


def _fused_sample_xla(
    rng, x, w, temperature, greedy, soft_cap, topk, block_size, kmax,
    logits_scale, vocab_rows,
) -> Dict[str, jnp.ndarray]:
    R, E = x.shape
    V = w.shape[0 if vocab_rows else 1]
    block = max(1, min(int(block_size), V))
    nbf, tail = divmod(V, block)
    t = jnp.maximum(temperature.astype(jnp.float32), 1e-6)

    carry = {
        "m": jnp.full((R,), _MASK, jnp.float32),
        "l": jnp.zeros((R,), jnp.float32),
        "am_v": jnp.full((R,), _MASK, jnp.float32),
        "am_i": jnp.zeros((R,), jnp.int32),
        "g_p": jnp.full((R,), _MASK, jnp.float32),
        "g_w": jnp.zeros((R,), jnp.float32),
        "g_i": jnp.zeros((R,), jnp.int32),
    }
    if topk is not None:
        carry["topv"] = jnp.full((R, kmax), _MASK, jnp.float32)
        carry["topi"] = jnp.zeros((R, kmax), jnp.int32)

    v_axis = 0 if vocab_rows else 1

    def _logits(w_blk):
        # a block of [E, V], or of [V, E] contracted over its E
        out = jax.lax.dot_general(
            x, w_blk, (((1,), (1 - v_axis,)), ((), ())),
            preferred_element_type=jnp.float32,
        )
        if logits_scale != 1.0:
            out = out / logits_scale
        if soft_cap is not None and soft_cap > 0:
            out = jnp.tanh(out / soft_cap) * soft_cap
        return out

    if nbf > 0:
        def body(c, j):
            w_blk = jax.lax.dynamic_slice_in_dim(w, j * block, block, v_axis)
            c = _update_block(
                c, _logits(w_blk), j * block, jax.random.fold_in(rng, j),
                t, kmax,
            )
            return c, None

        carry, _ = jax.lax.scan(body, carry, jnp.arange(nbf))
    if tail:
        w_blk = jax.lax.slice_in_dim(w, nbf * block, V, axis=v_axis)
        carry = _update_block(
            carry, _logits(w_blk), nbf * block,
            jax.random.fold_in(rng, nbf), t, kmax,
        )

    norm = carry["m"] + jnp.log(carry["l"])
    tokens = jnp.where(greedy, carry["am_i"], carry["g_i"])
    lp = jnp.where(
        greedy, carry["am_v"] / t - norm, carry["g_w"] - norm
    )
    if topk is not None:
        kk = jnp.clip(topk, 1, kmax)[:, None]
        pos = jnp.arange(kmax)[None, :]
        masked = jnp.where(pos < kk, carry["topv"], NEG_INF)
        choice = jax.random.categorical(
            jax.random.fold_in(rng, nbf + 1), masked, axis=-1
        )
        tok_k = jnp.take_along_axis(
            carry["topi"], choice[:, None], axis=-1
        )[:, 0]
        lp_k = jnp.take_along_axis(masked, choice[:, None], axis=-1)[:, 0] \
            - jax.scipy.special.logsumexp(masked, axis=-1)
        use_k = (topk <= kmax) & ~greedy
        tokens = jnp.where(use_k, tok_k, tokens)
        lp = jnp.where(use_k, lp_k, lp)
    return {
        "tokens": tokens.astype(jnp.int32),
        "logprobs": lp.astype(jnp.float32),
        "argmax": carry["am_i"],
        "norm": norm,
    }


def fused_sample(
    rng: jax.Array,
    x: jnp.ndarray,                # [R, E] final-norm hidden states
    w: jnp.ndarray,                # [E, V] head weight (serving dtype),
                                   # or [V, E] with ``vocab_rows``
    temperature: jnp.ndarray,      # [R] f32 (0 => greedy slot)
    greedy: jnp.ndarray,           # [R] bool
    soft_cap: Optional[float] = None,
    topk: Optional[jnp.ndarray] = None,    # [R] i32; > TOPK_MAX => inactive
    block_size: Optional[int] = None,
    use_pallas: Optional[bool] = None,
    mesh=None,
    interpret: Optional[bool] = None,
    logits_scale: float = 1.0,
    vocab_rows: bool = False,
) -> Dict[str, jnp.ndarray]:
    """Sample one token per row without materializing ``[R, V]`` logits.

    Returns a dict: ``tokens`` [R] i32 (greedy rows: exact raw argmax;
    rows with active ``topk``: exact top-k sample; others: Gumbel-top-1
    categorical over the temperature-warped head), ``logprobs`` [R] f32
    w.r.t. the warped (and, for top-k rows, top-k-restricted) distribution
    — the same semantics ``sample_tokens`` reports — plus ``argmax`` [R]
    i32 (raw argmax) and ``norm`` [R] f32 (warped log-normalizer).

    ``use_pallas=None`` auto-detects: the TPU kernel runs when there is no
    top-k buffer and no mesh; everywhere else the streamed XLA path runs
    (same math, same memory shape — peak ``[R, block]``). Explicit
    ``use_pallas=True`` raises when the kernel cannot serve the request.
    ``block_size`` left ``None``: the kernel sizes its vocabulary block
    from the shapes (``ops/pallas/fused_sample.py:block_columns``), the XLA
    path takes 2048 columns.

    ``vocab_rows`` (STATIC) says ``w`` is ``[V, E]``: a tied embedding as
    the parameter tree stores it (``models/transformer.py:head_operand``),
    streamed in row blocks, never transposed. ``logits_scale`` (STATIC)
    divides the float32 logits before the soft cap
    (``cfg.logits_scaling``), as ``models/transformer.py:_head`` does.
    """
    R, E = x.shape
    V = w.shape[0 if vocab_rows else 1]
    if w.shape[1 if vocab_rows else 0] != E:
        raise ValueError(f"head weight {w.shape} does not match hidden {x.shape}")
    if use_pallas is None:
        use_pallas = (
            _platform() == "tpu"
            and mesh is None
            and topk is None
            and V >= 128
        )
    if use_pallas:
        if topk is not None:
            raise ValueError(
                "fused_sample pallas kernel does not maintain the top-k "
                "buffer; leave use_pallas unset so top-k rows take the "
                "streamed XLA epilogue"
            )
        if mesh is not None:
            raise ValueError(
                "fused_sample pallas kernel has no TP shard_map wiring; "
                "use the XLA epilogue under a mesh (GSPMD partitions the "
                "block matmuls)"
            )
        from areal_tpu.ops.pallas import fused_sample as _pk

        return _pk.fused_sample_pallas(
            rng, x, w, temperature, greedy, soft_cap=soft_cap,
            block_v=block_size, interpret=interpret,
            logits_scale=logits_scale, vocab_rows=vocab_rows,
        )
    return _fused_sample_xla(
        rng, x, w, temperature, greedy, soft_cap, topk,
        2048 if block_size is None else block_size, TOPK_MAX,
        logits_scale, vocab_rows,
    )

