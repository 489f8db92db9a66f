"""Draft-token proposers for speculative decoding.

The drafter runs INSIDE the generation engine's jitted decode chunk
(``gen/engine.py::_spec_chunk_fn``): ``propose`` must be a pure, traceable
function of device state — no host syncs, no data-dependent shapes. The
engine hands it the slot batch's resident token context and expects
``[B, K]`` proposed tokens back; the verify forward then scores all K+1
positions in one pass and ``sampling.spec_rejection_sample`` accepts a
prefix. Because acceptance is exactly distribution-preserving, a drafter
can NEVER corrupt outputs — only the accept rate (and therefore speed)
varies with its quality.

Shipped baseline: :class:`NGramDrafter`, self-drafting via on-device
suffix lookup over the slot's resident context (prompt + generated tokens
— the ``ctx_tokens`` buffer the engine maintains), falling back to the
engine-provided greedy-from-last-logits hint when no match exists. Needs
no second model, which makes it free to serve: repetitive/structured
generations (math derivations, code, re-quoted context) are its sweet
spot.

:class:`TransformerDrafter` is the step past self-drafting: a small
TP-sharded draft MODEL on the serving mesh, autoregressively proposing K
tokens through ``decode_step_paged`` on its own params and its OWN paged
KV pool (same page indices as the target pool, so pages allocate/free in
lockstep — see ``gen/pages.py``). It declares ``deterministic = False``
and ``provides_q_logprobs = True``: every proposal comes with the
per-position proposal distribution, which feeds the general-q branch of
``sampling.spec_rejection_sample`` — still exactly distribution-
preserving, still PPO-safe.
"""

import dataclasses
from typing import Any, Optional, Tuple

import jax
import jax.numpy as jnp


class Drafter:
    """Interface: propose K draft tokens per slot from resident context.

    ``deterministic = True`` declares one-hot proposals (the rejection
    sampler then needs no proposal distribution). A sampled drafter
    (``deterministic = False``) MUST set ``provides_q_logprobs = True``
    and return its proposal distribution alongside the tokens — the
    engine refuses sampled drafters that don't, because accepting their
    proposals without q would silently bias generation toward the
    drafter (PPO corruption). ``propose`` executes under ``jax.jit``
    inside a ``lax.scan`` body.

    ``k`` is a STATIC argument the engine may change between chunks:
    adaptive spec-K (``AREAL_SPEC_K_ADAPT``) retunes the draft length
    from the live accept-length histogram, so ``propose`` /
    ``propose_model`` must be pure in ``k`` (no k-dependent Python state)
    — each K gets its own jitted spec-chunk specialization, bounded by
    the engine's fixed choice set, never by traffic.
    """

    deterministic: bool = True
    provides_q_logprobs: bool = False

    def propose(
        self,
        ctx_tokens: jnp.ndarray,   # [B, S] i32; [b, :lens[b]+1] is valid
        lens: jnp.ndarray,         # [B] i32; ctx_tokens[b, lens[b]] = last token
        fallback: jnp.ndarray,     # [B] i32 greedy-from-last-logits hint
        k: int,
    ) -> jnp.ndarray:              # [B, k] i32
        raise NotImplementedError


@dataclasses.dataclass(frozen=True)
class NGramDrafter(Drafter):
    """Self-drafting suffix lookup: find the most recent earlier occurrence
    of the context's trailing bigram (then unigram) and propose the K
    tokens that followed it; positions past the match's continuation — or
    slots with no match at all — fill with the ``fallback`` token.

    Cost: two ``[B, S]`` comparisons + one gather per spec step — noise
    next to the verify forward. The bigram→unigram cascade is the standard
    prompt-lookup-decoding heuristic (≈ llama.cpp / transformers
    ``prompt_lookup_num_tokens``)."""

    deterministic: bool = True

    def propose(self, ctx_tokens, lens, fallback, k):
        B, S = ctx_tokens.shape
        rows = jnp.arange(B)
        last = ctx_tokens[rows, jnp.clip(lens, 0, S - 1)]
        prev = ctx_tokens[rows, jnp.clip(lens - 1, 0, S - 1)]
        # bigram (prev, last) at (j, j+1): continuation starts at j+2 and
        # must begin inside the valid region (j+2 <= lens); lens >= 1
        # guards the prev read
        j = jnp.arange(S - 1)[None, :]
        big = (
            (ctx_tokens[:, :-1] == prev[:, None])
            & (ctx_tokens[:, 1:] == last[:, None])
            & (j + 1 < lens[:, None])
            & (lens >= 1)[:, None]
        )
        m2 = jnp.max(jnp.where(big, j, -1), axis=1)          # most recent
        ju = jnp.arange(S)[None, :]
        uni = (ctx_tokens == last[:, None]) & (ju < lens[:, None])
        m1 = jnp.max(jnp.where(uni, ju, -1), axis=1)
        start = jnp.where(m2 >= 0, m2 + 2, jnp.where(m1 >= 0, m1 + 1, -1))
        offs = start[:, None] + jnp.arange(k)[None, :]       # [B, k]
        in_ctx = (start[:, None] >= 0) & (offs <= lens[:, None])
        cont = jnp.take_along_axis(
            ctx_tokens, jnp.clip(offs, 0, S - 1), axis=1
        )
        return jnp.where(in_ctx, cont, fallback[:, None]).astype(jnp.int32)


class TransformerDrafter(Drafter):
    """A small transformer draft MODEL proposing K tokens autoregressively
    inside the jitted spec chunk.

    The engine owns the heavy lifting: it prepares (casts + TP-shards)
    ``params`` onto the serving mesh through the same
    ``parallel/mesh.py`` logical-axis rules as the target, carries the
    draft's OWN :class:`~areal_tpu.models.transformer.PagedKVCache` in
    its state pytree (addressed by the SAME page table as the target
    pool, so draft pages allocate/free in lockstep for free), and calls
    :meth:`propose_model` from inside the spec chunk's scan body.

    Each of the K proposal steps is one ``decode_step_paged`` on the
    draft params: sample ``d_i ~ q_i`` (plain temperature-scaled draft
    distribution; argmax for greedy slots), write its KV, feed it back.
    The returned ``q_logprobs`` feed the general-q branch of
    ``spec_rejection_sample`` — acceptance stays exactly distribution-
    preserving for ANY proposal distribution, so a bad draft model can
    only lower the accept rate, never perturb outputs.

    ``cfg.vocab_size`` must equal the target's (tokens interchange);
    the engine validates at construction. ``kv_dtype`` optionally
    int8-quantizes the draft pool through the same ``kv_dtype`` path as
    the target pool (``AREAL_SPEC_DRAFT_KV_DTYPE``).
    """

    deterministic = False
    provides_q_logprobs = True

    def __init__(self, cfg, params: Any, kv_dtype: Optional[str] = None):
        self.cfg = cfg
        self.params = params        # host pytree; engine prepares it
        self.kv_dtype = kv_dtype

    @classmethod
    def from_hf(cls, path: str, kv_dtype: Optional[str] = None):
        """Load a draft checkpoint (HF dir) via ``models/hf.py`` — the
        ``AREAL_SPEC_DRAFT_MODEL`` deployment path."""
        from areal_tpu.models import hf as hf_conv

        cfg, params = hf_conv.load_hf_checkpoint(path)
        return cls(cfg, params, kv_dtype=kv_dtype)

    @classmethod
    def shared_prefix(cls, cfg, params, n_layers: int,
                      kv_dtype: Optional[str] = None):
        """Test constructor: the draft is the first ``n_layers`` of the
        target's stacked params (shared embeddings + head). A stand-in
        for a distilled draft when no checkpoint exists: predictive only
        when the target's later layers refine rather than overturn the
        early layers' logits (true of trained models; for seeded weights
        see the damping recipe in ROADMAP D1). Real deployments point ``AREAL_SPEC_DRAFT_MODEL`` at a distilled
        checkpoint instead."""
        if cfg.n_passes > 1:
            raise ValueError(
                f"shared-prefix draft: the target runs its {cfg.n_layers} "
                f"layers {cfg.n_passes} times (n_passes), and 'the first "
                "n_layers' of a looped stack is no model"
            )
        if not 0 < n_layers <= cfg.n_layers:
            raise ValueError(
                f"shared-prefix draft needs 0 < n_layers <= {cfg.n_layers}, "
                f"got {n_layers}"
            )
        dcfg = dataclasses.replace(cfg, n_layers=n_layers)
        dparams = dict(params)
        dparams["layers"] = jax.tree.map(
            lambda x: x[:n_layers], params["layers"]
        )
        return cls(dcfg, dparams, kv_dtype=kv_dtype)

    def propose(self, ctx_tokens, lens, fallback, k):  # pragma: no cover
        raise NotImplementedError(
            "TransformerDrafter proposes through propose_model (it needs "
            "its params and paged KV cache, not just the token context)"
        )

    def propose_model(
        self,
        draft_params,
        cache,                     # draft PagedKVCache
        last_tokens: jnp.ndarray,  # [B] i32 pending token per slot
        table: jnp.ndarray,        # [B, W] page table (shared with target)
        lens: jnp.ndarray,         # [B] i32 resident tokens per slot
        write_ok: jnp.ndarray,     # [B, K+1] bool: position i's KV may land
        sp,                        # SamplingParams
        rng: jax.Array,
        k: int,
        use_pallas: Optional[bool] = None,
        mesh=None,
        logits_sharding=None,
    ) -> Tuple[jnp.ndarray, jnp.ndarray, Any]:
        """K autoregressive draft steps. Returns ``(draft [B, K] i32,
        q_logprobs [B, K, V] f32, cache)`` — the tokens, the proposal
        distribution each was sampled from, and the draft cache with
        positions ``lens..lens+K`` written where ``write_ok`` allows
        (the engine's acceptance-agnostic residency bound: rejected
        drafts' KV lands beyond the post-acceptance ``lens``, never
        read, overwritten later — same contract as the target's
        ``verify_step_paged`` scatter). All K+1 chunk positions are
        written: the K steps write the tokens they CONSUME (``last``,
        ``d_1..d_{K-1}``), and a final headless step writes ``d_K``'s —
        on a fully-accepted step ``lens`` advances past ``d_K``, so
        skipping it would leave a permanently resident garbage position
        the next proposal's attention reads (partial accepts would
        overwrite it; full accepts never do).

        Pure and traceable: executes inside the engine's jitted spec
        chunk, no host syncs. ``write_ok[:, i]`` is monotone per slot
        (once False, stays False), so the per-step ``lens`` advance
        tracks the written prefix exactly.
        """
        from areal_tpu.gen.sampling import _plain_temperature
        from areal_tpu.models import transformer as tfm

        greedy = sp.temperature <= 0.0
        keys = jax.random.split(rng, k)
        tok = last_tokens
        d_lens = lens
        drafts, qlps = [], []
        for i in range(k):
            logits, cache, d_lens = tfm.decode_step_paged(
                draft_params, self.cfg, cache, tok, table, d_lens,
                write_ok[:, i], use_pallas=use_pallas, mesh=mesh,
            )
            if logits_sharding is not None:
                # TP serving: one explicit all-gather so the per-position
                # sampling below runs replicated (the target chunk applies
                # the same constraint to its verify logits)
                logits = jax.lax.with_sharding_constraint(
                    logits, logits_sharding
                )
            q_logits = _plain_temperature(logits, sp)      # [B, V] f32
            q_lp = jax.nn.log_softmax(q_logits, axis=-1)
            sampled = jax.random.categorical(keys[i], q_logits, axis=-1)
            tok = jnp.where(
                greedy, jnp.argmax(logits, axis=-1), sampled
            ).astype(jnp.int32)
            drafts.append(tok)
            qlps.append(q_lp)
        # d_K's own KV (see docstring): headless — no logits, no sample
        _, cache, _ = tfm.decode_step_paged(
            draft_params, self.cfg, cache, tok, table, d_lens,
            write_ok[:, k], use_pallas=use_pallas, mesh=mesh,
            with_head=False,
        )
        return (
            jnp.stack(drafts, axis=1),
            jnp.stack(qlps, axis=1),
            cache,
        )
