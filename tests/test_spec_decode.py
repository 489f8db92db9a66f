"""Speculative decoding: distribution preservation, engine parity, and
composition with the chunked interruptible engine's guarantees.

The load-bearing contracts (docs/performance.md "Speculative decoding"):
- greedy spec decode is TOKEN-IDENTICAL to vanilla decode (acceptance is
  ``draft == argmax`` and the residual is the argmax);
- sampled-mode acceptance is exactly distribution-preserving (chi-square
  on a toy vocab, for both one-hot and general-q proposals);
- spec chunks compose with pause/resume interruption, hot weight swap,
  chunk pipelining, and the bounded-compile discipline.
"""

import dataclasses

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from areal_tpu.base import metrics as metrics_mod
from areal_tpu.gen.drafter import NGramDrafter, TransformerDrafter
from areal_tpu.gen.engine import GenerationEngine, GenRequest
from areal_tpu.gen.sampling import SamplingParams, spec_rejection_sample
from areal_tpu.models import transformer as tfm
from areal_tpu.models.config import ModelConfig

CFG = ModelConfig(
    n_layers=2, n_q_heads=4, n_kv_heads=2, head_dim=8, hidden_dim=32,
    intermediate_dim=64, vocab_size=128, dtype="float32",
)


@pytest.fixture(scope="module")
def params():
    return tfm.init_params(CFG, jax.random.key(5))


def _engine(params, spec, **kw):
    kw.setdefault("max_slots", 2)
    kw.setdefault("max_seqlen", 128)
    return GenerationEngine(CFG, params, spec_decode=spec, **kw)


def _prompts(rng, sizes=(5, 9, 3)):
    return [[int(x) for x in rng.integers(1, 128, size=n)] for n in sizes]


class TestGreedyParity:
    def test_greedy_spec_matches_vanilla(self, params, rng):
        """Greedy spec decode must be token-exact vs vanilla decode: same
        output ids, same finish reasons, same (warped-target) logprobs."""
        prompts = _prompts(rng)
        outs = []
        for spec in (False, True):
            eng = _engine(params, spec, max_slots=4, spec_k=3)
            for i, p in enumerate(prompts):
                eng.submit(GenRequest(
                    rid=f"r{i}", input_ids=p, max_new_tokens=10 + i,
                    greedy=True,
                ))
            outs.append({o.rid: o for o in eng.run_until_done(decode_steps=3)})
        assert set(outs[0]) == set(outs[1])
        for rid in outs[0]:
            assert outs[0][rid].output_ids == outs[1][rid].output_ids, rid
            assert outs[0][rid].finish_reason == outs[1][rid].finish_reason
            np.testing.assert_allclose(
                outs[0][rid].output_logprobs, outs[1][rid].output_logprobs,
                atol=1e-4,
            )

    def test_spec_stop_tokens_truncate_mid_draft(self, params, rng):
        """A stop token accepted INSIDE a draft chain must truncate the
        emission exactly where vanilla decode stops (stop included)."""
        prompt = [int(x) for x in rng.integers(1, 128, size=5)]
        ref_eng = _engine(params, False)
        ref_eng.submit(GenRequest(
            rid="ref", input_ids=prompt, max_new_tokens=12, greedy=True,
        ))
        ref = ref_eng.run_until_done(decode_steps=4)[0].output_ids
        stop = ref[4]
        eng = _engine(params, True, spec_k=4, stop_token_ids=[stop])
        eng.submit(GenRequest(
            rid="a", input_ids=prompt, max_new_tokens=12, greedy=True,
        ))
        outs = eng.run_until_done(decode_steps=4)
        assert outs[0].finish_reason == "stop"
        assert outs[0].output_ids == ref[:5]

    def test_spec_min_new_tokens_suppresses_stop(self, params, rng):
        prompt = [int(x) for x in rng.integers(1, 128, size=5)]
        ref_eng = _engine(params, False)
        ref_eng.submit(GenRequest(
            rid="ref", input_ids=prompt, max_new_tokens=8, greedy=True,
        ))
        ref = ref_eng.run_until_done(decode_steps=4)[0].output_ids
        stop = ref[1]  # would stop at the 2nd token without suppression
        eng = _engine(params, True, spec_k=3, stop_token_ids=[stop])
        eng.submit(GenRequest(
            rid="a", input_ids=prompt, max_new_tokens=8, min_new_tokens=4,
            greedy=True,
        ))
        outs = eng.run_until_done(decode_steps=4)
        # the early stop is suppressed below min_new_tokens; generation
        # runs on until a later stop occurrence or the cap
        assert len(outs[0].output_ids) >= 4
        assert outs[0].output_ids[:4] == ref[:4]

    def test_verify_logits_match_sequential_decode(self, params, rng):
        """The multi-token verify forward must produce the same logits as
        running decode_step_paged sequentially (teacher-forced) — the
        numerical anchor under everything above."""
        # (a page of 16: the four positions verified below, 7..10, lie in
        # the page the slot holds; a slot takes its pages as it grows, and
        # this test writes past what the engine has dispatched)
        eng = _engine(params, False, max_slots=2, page_size=16)
        prompt = [int(x) for x in rng.integers(1, 128, size=6)]
        eng.submit(GenRequest(
            rid="a", input_ids=prompt, max_new_tokens=8, greedy=True,
        ))
        eng.step(decode_steps=2)   # some resident context
        assert eng._held[0, 0, 0] and eng._lens_host[0] + 4 <= 16
        state = eng.state
        table = jnp.asarray(eng._table_host)
        drafts = jnp.asarray(
            rng.integers(1, 128, size=(eng.B, 3)), jnp.int32
        )
        chunk = jnp.concatenate([state.last_tokens[:, None], drafts], axis=1)
        C = int(chunk.shape[1])
        n_new = jnp.where(state.active, C, 0).astype(jnp.int32)
        v_logits, _ = tfm.verify_step_paged(
            params, CFG, state.cache, chunk, table, state.lens, n_new, n_new,
        )
        # sequential teacher-forced decode over the same tokens
        cache, lens = state.cache, state.lens
        for i in range(C):
            logits_i, cache, lens = tfm.decode_step_paged(
                params, CFG, cache, chunk[:, i], table, lens, state.active,
                use_pallas=False,
            )
            b = 0  # slot 0 is the active one
            np.testing.assert_allclose(
                np.asarray(v_logits)[b, i], np.asarray(logits_i)[b],
                atol=2e-4, rtol=2e-4,
            )


class TestDistributionPreservation:
    def _marginal(self, key, logits, draft, sp, n, q_logprobs=None):
        """Empirical distribution of the FIRST emitted token over n runs.

        With a general proposal, the theorem requires the draft be DRAWN
        from it — so each run samples its own draft from ``q_logprobs``;
        one-hot proposals keep the fixed draft (the delta's only sample).
        """
        def one(k):
            d = draft
            if q_logprobs is not None:
                kd, k = jax.random.split(k)
                d = jax.vmap(
                    lambda kk, ql: jax.random.categorical(kk, ql, axis=-1),
                    in_axes=(None, 1), out_axes=1,
                )(kd, q_logprobs).astype(jnp.int32)
            _, tokens, _, _ = spec_rejection_sample(
                k, logits, d, sp, warp=False, q_logprobs=q_logprobs
            )
            return tokens[0, 0]

        toks = jax.vmap(one)(jax.random.split(key, n))
        V = logits.shape[-1]
        return np.bincount(np.asarray(toks), minlength=V) / n

    @pytest.mark.parametrize("general_q", [False, True])
    def test_first_token_marginal_chi_square(self, general_q):
        """The first emitted token (accepted draft OR residual) must be
        distributed exactly as the target — for one-hot proposals and for
        a general proposal distribution the drafts are sampled from."""
        V, K = 16, 2
        rng = np.random.default_rng(0)
        logits = jnp.asarray(
            rng.normal(size=(1, K + 1, V)), jnp.float32
        )
        draft = jnp.asarray([[3, 7]], jnp.int32)
        sp = SamplingParams.filled(1)
        q_lp = None
        if general_q:
            q = rng.normal(size=(1, K, V)).astype(np.float32)
            q_lp = jnp.asarray(jax.nn.log_softmax(jnp.asarray(q), axis=-1))
        n = 20000
        emp = self._marginal(
            jax.random.key(1), logits, draft, sp, n, q_logprobs=q_lp
        )
        want = np.asarray(jax.nn.softmax(logits[0, 0]))
        chi2 = (n * (emp - want) ** 2 / np.maximum(want, 1e-9)).sum()
        # df = 15; p=0.001 critical value ~37.7 — generous margin
        assert chi2 < 45.0, (chi2, emp, want)

    def test_accepted_prefix_then_residual_layout(self):
        """accept_len semantics: positions < accept_len are draft tokens,
        position accept_len the residual; greedy accepts iff argmax."""
        V = 8
        logits = np.full((1, 3, V), -10.0, np.float32)
        logits[0, 0, 2] = 10.0   # argmax 2
        logits[0, 1, 5] = 10.0   # argmax 5
        logits[0, 2, 1] = 10.0   # bonus argmax 1
        sp = SamplingParams.filled(1, temperature=0.0)
        # full acceptance: drafts match argmax chain -> bonus emitted
        a, toks, _, _ = spec_rejection_sample(
            jax.random.key(0), jnp.asarray(logits),
            jnp.asarray([[2, 5]], jnp.int32), sp, warp=False,
        )
        assert int(a[0]) == 2
        assert toks[0, :3].tolist() == [2, 5, 1]
        # first draft wrong -> rejected immediately, residual = argmax
        a, toks, _, _ = spec_rejection_sample(
            jax.random.key(0), jnp.asarray(logits),
            jnp.asarray([[4, 5]], jnp.int32), sp, warp=False,
        )
        assert int(a[0]) == 0
        assert int(toks[0, 0]) == 2

    def test_sampled_spec_engine_runs_and_varies(self, params):
        """Stochastic spec decode through the full engine: reproducible
        per-seed, diverse across slots (the vanilla sampling contract)."""
        outs = {}
        for run in range(2):
            eng = _engine(params, True, max_slots=4, spec_k=3, seed=7)
            for i in range(4):
                eng.submit(GenRequest(
                    rid=f"s{i}", input_ids=[5, 6, 7], max_new_tokens=8,
                    temperature=1.0, top_p=0.95,
                ))
            outs[run] = {
                o.rid: o.output_ids
                for o in eng.run_until_done(decode_steps=2)
            }
        assert outs[0] == outs[1]                       # seeded: reproducible
        assert len(set(map(tuple, outs[0].values()))) > 1  # slots differ


class TestComposition:
    def test_pause_mid_spec_chunk_harvests_valid_partial(self, params, rng):
        """pause() mid-spec-generation yields an 'interrupted' partial that
        is a PREFIX of the uninterrupted greedy chain, and resubmission
        completes it exactly (the partial-rollout protocol)."""
        prompt = [int(x) for x in rng.integers(1, 128, size=5)]
        ref_eng = _engine(params, False)
        ref_eng.submit(GenRequest(
            rid="ref", input_ids=prompt, max_new_tokens=12, greedy=True,
        ))
        ref = ref_eng.run_until_done(decode_steps=4)[0].output_ids

        eng = _engine(params, True, spec_k=3)
        eng.submit(GenRequest(
            rid="a", input_ids=prompt, max_new_tokens=12, greedy=True,
        ))
        eng.step(decode_steps=1)
        parts = eng.pause()
        assert len(parts) == 1 and parts[0].finish_reason == "interrupted"
        got = parts[0].output_ids
        assert 0 < len(got) < 12
        assert got == ref[: len(got)]
        eng.resume()
        eng.submit(GenRequest(
            rid="a2", input_ids=prompt + got,
            max_new_tokens=12 - len(got), greedy=True,
        ))
        outs = eng.run_until_done(decode_steps=4)
        assert got + outs[0].output_ids == ref

    def test_update_params_between_spec_chunks_bumps_version(
        self, params, monkeypatch
    ):
        # through the literal env knob (AREAL_SPEC_DECODE=1), not the
        # ctor override — the path a deployed fleet takes
        monkeypatch.setenv("AREAL_SPEC_DECODE", "1")
        monkeypatch.setenv("AREAL_SPEC_K", "2")
        eng = _engine(params, None, max_slots=1)
        assert eng.spec is True and eng.spec_k == 2
        eng.submit(GenRequest(
            rid="a", input_ids=[1, 2, 3], max_new_tokens=2, greedy=True,
        ))
        outs = eng.run_until_done(decode_steps=2)
        assert outs[0].version == 0
        new_params = tfm.init_params(CFG, jax.random.key(9))
        eng.update_params(new_params, version=3)
        assert len(eng.prefix) == 0
        eng.submit(GenRequest(
            rid="b", input_ids=[1, 2, 3], max_new_tokens=2, greedy=True,
        ))
        outs = eng.run_until_done(decode_steps=2)
        assert outs[0].version == 3

    def test_spec_pipelined_matches_unpipelined(self, params, rng):
        prompts = _prompts(rng, sizes=(5, 9, 3, 7))
        outs = []
        for pipelined in (False, True):
            eng = _engine(
                params, True, max_slots=4, spec_k=3,
                pipeline_chunks=pipelined,
            )
            for i, p in enumerate(prompts):
                eng.submit(GenRequest(
                    rid=f"r{i}", input_ids=p, max_new_tokens=10 + i,
                    greedy=True,
                ))
            outs.append({
                o.rid: o for o in eng.run_until_done(decode_steps=2)
            })
        assert set(outs[0]) == set(outs[1])
        for rid in outs[0]:
            assert outs[0][rid].output_ids == outs[1][rid].output_ids, rid
            assert outs[0][rid].finish_reason == outs[1][rid].finish_reason

    def test_mixed_spec_vanilla_traffic_bounded_compiles(self, params, rng):
        """Flipping spec on/off between chunks (one engine, one state
        pytree) must not grow jit specializations past the warm set —
        the n_compiles discipline extended to mixed traffic."""
        eng = _engine(params, False, max_slots=4, max_seqlen=256,
                      page_size=16, spec_k=3)
        def burst(tag, plens):
            for i, plen in enumerate(plens):
                eng.submit(GenRequest(
                    rid=f"{tag}{i}",
                    input_ids=[int(x) for x in rng.integers(1, 128, plen)],
                    max_new_tokens=6, greedy=True,
                ))
            eng.run_until_done(decode_steps=3)

        burst("v", [3, 9, 17, 33])       # warm vanilla
        eng.spec = True
        burst("s", [3, 9, 17, 33])       # warm spec
        eng.spec = False
        burst("v2", [5, 21])
        eng.spec = True
        warmed = eng.n_compiles()
        # fresh prompt lengths + more toggles: no new specializations
        eng.spec = False
        burst("v3", [11, 29, 60])
        eng.spec = True
        burst("s2", [7, 45, 80])
        assert eng.n_compiles() == warmed

    def test_tp2_spec_greedy_matches_single_device(self, params, rng):
        """Spec decode on a 2-way `model` mesh (sampling replicated after
        the logits all-gather) must match the unsharded engine token for
        token."""
        from jax.sharding import Mesh

        mesh = Mesh(np.array(jax.devices()[:2]), ("model",))
        prompts = _prompts(rng)
        eng1 = _engine(params, True, max_slots=4, spec_k=3)
        eng2 = GenerationEngine(
            CFG, params, max_slots=4, max_seqlen=128,
            spec_decode=True, spec_k=3, mesh=mesh,
        )
        for eng in (eng1, eng2):
            for i, p in enumerate(prompts):
                eng.submit(GenRequest(
                    rid=f"r{i}", input_ids=p, max_new_tokens=8, greedy=True,
                ))
        o1 = {o.rid: o for o in eng1.run_until_done(decode_steps=2)}
        o2 = {o.rid: o for o in eng2.run_until_done(decode_steps=2)}
        assert set(o1) == set(o2)
        for rid in o1:
            assert o1[rid].output_ids == o2[rid].output_ids, rid

    def test_spec_telemetry_counters(self, params, rng):
        metrics_mod.counters.clear(metrics_mod.GEN_SPEC_DRAFT_TOKENS)
        metrics_mod.counters.clear(metrics_mod.GEN_SPEC_ACCEPTED_TOKENS)
        metrics_mod.counters.clear(metrics_mod.GEN_SPEC_ACCEPT_LEN)
        eng = _engine(params, True, spec_k=3)
        # a repetitive prompt: the n-gram drafter should accept something
        prompt = [7, 8, 9] * 6
        eng.submit(GenRequest(
            rid="a", input_ids=prompt, max_new_tokens=12, greedy=True,
        ))
        eng.run_until_done(decode_steps=2)
        drafted = eng.stats["spec_draft_tokens"]
        accepted = eng.stats["spec_accepted_tokens"]
        assert drafted > 0
        assert 0 <= accepted <= drafted
        assert metrics_mod.counters.get(
            metrics_mod.GEN_SPEC_DRAFT_TOKENS
        ) == drafted
        h = metrics_mod.counters.histogram(metrics_mod.GEN_SPEC_ACCEPT_LEN)
        assert h is not None and h.count > 0


def test_nondeterministic_drafter_rejected_at_construction(params):
    """Sampled drafters must declare provides_q_logprobs (and route
    through the model-drafter interface): one without q would silently
    bias generation toward its proposals (the distribution-preservation
    guarantee) — it must fail loudly, while drafters that DO supply q
    (TransformerDrafter) construct fine."""
    from areal_tpu.gen.drafter import Drafter

    class SampledDrafter(Drafter):
        # plain subclass, not the frozen dataclass: its generated __init__
        # would pin the instance attribute back to the dataclass default
        deterministic = False

        def propose(self, ctx_tokens, lens, fallback, k):  # pragma: no cover
            raise AssertionError("never reached")

    with pytest.raises(NotImplementedError, match="q_logprobs"):
        GenerationEngine(
            CFG, params, max_slots=2, max_seqlen=64,
            spec_decode=True, drafter=SampledDrafter(),
        )

    # declaring q without the propose_model wiring is equally loud: the
    # engine would otherwise call propose() and its q would never reach
    # the rejection sampler
    class LyingDrafter(Drafter):
        deterministic = False
        provides_q_logprobs = True

        def propose(self, ctx_tokens, lens, fallback, k):  # pragma: no cover
            raise AssertionError("never reached")

    with pytest.raises(NotImplementedError, match="TransformerDrafter"):
        GenerationEngine(
            CFG, params, max_slots=2, max_seqlen=64,
            spec_decode=True, drafter=LyingDrafter(),
        )

    # the relaxed guard's positive side: a sampled drafter that supplies
    # q through the model interface constructs (and serves) fine
    eng = GenerationEngine(
        CFG, params, max_slots=2, max_seqlen=64, spec_decode=True,
        drafter=TransformerDrafter.shared_prefix(CFG, params, 1),
    )
    assert eng._draft is not None

    # vocab mismatch is a construction error, not a runtime surprise
    bad_cfg = dataclasses.replace(CFG, vocab_size=64, n_layers=1)
    with pytest.raises(ValueError, match="vocab"):
        GenerationEngine(
            CFG, params, max_slots=2, max_seqlen=64, spec_decode=True,
            drafter=TransformerDrafter(
                bad_cfg, tfm.init_params(bad_cfg, jax.random.key(0))
            ),
        )


def test_env_draft_model_ignored_when_spec_disabled(params, monkeypatch):
    """A fleet-wide AREAL_SPEC_DRAFT_MODEL must not make a spec-disabled
    engine pay for a draft model (pool HBM + a per-vanilla-step
    maintenance sweep): the env-knob checkpoint is only resolved when
    spec decode is on, so construction with spec off never even touches
    the path (a bogus one proves it)."""
    from areal_tpu.base import constants

    monkeypatch.setenv(constants.SPEC_DRAFT_MODEL_ENV, "/nonexistent/draft")
    eng = GenerationEngine(
        CFG, params, max_slots=2, max_seqlen=64, spec_decode=False,
    )
    assert eng._draft is None
    assert isinstance(eng.drafter, NGramDrafter)
    assert eng.state.draft_cache is None
    assert eng.draft_kv_pool_bytes() == 0


def test_draft_dtype_coerced_into_drafter_cfg(params):
    """The engine coerces a draft checkpoint's dtype to the target's —
    and must write it back into the drafter, because propose_model runs
    the draft forward under the DRAFTER's cfg: leaving the checkpoint
    dtype there would compute spec-chunk proposals in one dtype while
    the vanilla chunk's maintenance step writes KV in another."""
    dcfg = dataclasses.replace(CFG, n_layers=1, dtype="bfloat16")
    drafter = TransformerDrafter(
        dcfg, tfm.init_params(dcfg, jax.random.key(7), dtype="bfloat16")
    )
    eng = GenerationEngine(
        CFG, params, max_slots=2, max_seqlen=64, spec_decode=True,
        drafter=drafter,
    )
    assert eng.draft_cfg.dtype == CFG.dtype == "float32"
    assert eng.drafter.cfg.dtype == "float32"
    leaf = jax.tree.leaves(eng.draft_params)[0]
    assert leaf.dtype == jnp.float32


class TestTransformerDrafter:
    """Draft-MODEL speculative decoding: a small transformer proposes K
    tokens autoregressively inside the jitted chunk, with its own paged
    KV pool riding the engine state in lockstep with the target's, and
    its proposal distribution feeding the general-q rejection sampler."""

    def _draft_engine(self, params, n_layers=1, drafter=None, **kw):
        drafter = drafter or TransformerDrafter.shared_prefix(
            CFG, params, n_layers
        )
        return _engine(params, True, drafter=drafter, **kw)

    def test_greedy_token_exact_vs_vanilla_any_draft(self, params, rng):
        """Greedy draft-model spec decode must be token-exact vs vanilla
        — even when the draft is an INDEPENDENT random-init model whose
        proposals are garbage (acceptance can only cost speed, never
        correctness), and with the q_accept_prob telemetry folding."""
        metrics_mod.counters.clear(metrics_mod.GEN_SPEC_Q_ACCEPT_PROB)
        prompts = _prompts(rng)
        dcfg = dataclasses.replace(CFG, n_layers=1)
        garbage = TransformerDrafter(
            dcfg, tfm.init_params(dcfg, jax.random.key(123))
        )
        runs = {}
        for name, eng in (
            ("vanilla", _engine(params, False, max_slots=4)),
            ("garbage", self._draft_engine(
                params, drafter=garbage, max_slots=4, spec_k=3)),
            ("prefix", self._draft_engine(params, max_slots=4, spec_k=3)),
        ):
            for i, p in enumerate(prompts):
                eng.submit(GenRequest(
                    rid=f"r{i}", input_ids=p, max_new_tokens=10 + i,
                    greedy=True,
                ))
            runs[name] = {
                o.rid: o for o in eng.run_until_done(decode_steps=3)
            }
        for name in ("garbage", "prefix"):
            assert set(runs["vanilla"]) == set(runs[name])
            for rid, ref in runs["vanilla"].items():
                got = runs[name][rid]
                assert ref.output_ids == got.output_ids, (name, rid)
                assert ref.finish_reason == got.finish_reason
                np.testing.assert_allclose(
                    ref.output_logprobs, got.output_logprobs, atol=1e-4
                )
        h = metrics_mod.counters.histogram(
            metrics_mod.GEN_SPEC_Q_ACCEPT_PROB
        )
        assert h is not None and h.count > 0

    def test_first_token_marginal_chi_square_engine_general_q(self):
        """The full engine path — draft model proposes sampled tokens
        from q, verify scores, general-q rejection accepts — must leave
        the FIRST emitted token distributed exactly as the target
        (chi-square on a 32-token vocab against the target's softmax)."""
        V32 = ModelConfig(
            n_layers=2, n_q_heads=2, n_kv_heads=2, head_dim=8,
            hidden_dim=16, intermediate_dim=32, vocab_size=32,
            dtype="float32",
        )
        tparams = tfm.init_params(V32, jax.random.key(3))
        dcfg = dataclasses.replace(V32, n_layers=1)
        drafter = TransformerDrafter(
            dcfg, tfm.init_params(dcfg, jax.random.key(77))
        )
        eng = GenerationEngine(
            V32, tparams, max_slots=16, max_seqlen=32, spec_decode=True,
            spec_k=2, drafter=drafter, enable_prefix_cache=False,
        )
        prompt = [3, 9, 4, 1]
        n = 2048
        counts = np.zeros(32)
        r = 0
        while int(counts.sum()) < n:
            for i in range(16):
                eng.submit(GenRequest(
                    rid=f"{r}_{i}", input_ids=prompt, max_new_tokens=1,
                    temperature=1.0,
                ))
            for o in eng.run_until_done(decode_steps=1):
                counts[o.output_ids[0]] += 1
            r += 1
        T = len(prompt)
        logits = tfm.forward_packed(
            tparams, V32, jnp.asarray(prompt, jnp.int32),
            jnp.ones((T,), jnp.int32), jnp.arange(T, dtype=jnp.int32),
            remat=False,
        )[-1]
        want = np.asarray(jax.nn.softmax(logits))
        total = counts.sum()
        emp = counts / total
        chi2 = (total * (emp - want) ** 2 / np.maximum(want, 1e-9)).sum()
        # df = 31; p=0.001 critical value ~61.1 — generous margin (the
        # run is seeded, so this is a one-time calibration, not a flake)
        assert chi2 < 75.0, (chi2, emp, want)

    def test_tp2_draft_greedy_matches_single_device(self, params, rng):
        """Draft-model spec decode on a 2-way `model` mesh (draft params
        + draft pool sharded through the same rules as the target) must
        match the unsharded engine token for token."""
        from jax.sharding import Mesh

        mesh = Mesh(np.array(jax.devices()[:2]), ("model",))
        prompts = _prompts(rng)
        eng1 = self._draft_engine(params, max_slots=4, spec_k=3)
        eng2 = GenerationEngine(
            CFG, params, max_slots=4, max_seqlen=128, spec_decode=True,
            spec_k=3, mesh=mesh,
            drafter=TransformerDrafter.shared_prefix(CFG, params, 1),
        )
        for eng in (eng1, eng2):
            for i, p in enumerate(prompts):
                eng.submit(GenRequest(
                    rid=f"r{i}", input_ids=p, max_new_tokens=8, greedy=True,
                ))
        o1 = {o.rid: o for o in eng1.run_until_done(decode_steps=2)}
        o2 = {o.rid: o for o in eng2.run_until_done(decode_steps=2)}
        assert set(o1) == set(o2)
        for rid in o1:
            assert o1[rid].output_ids == o2[rid].output_ids, rid

    def test_draft_page_lockstep_under_pause_resume(self, params, rng):
        """Draft pages are the TARGET's pages (one index, two pools), so
        pause must release everything back to the pool, the interrupted
        partial must be a valid greedy prefix, and the resubmission —
        re-prefilling BOTH pools — must complete the chain exactly."""
        prompt = [int(x) for x in rng.integers(1, 128, size=5)]
        ref_eng = _engine(params, False)
        ref_eng.submit(GenRequest(
            rid="ref", input_ids=prompt, max_new_tokens=12, greedy=True,
        ))
        ref = ref_eng.run_until_done(decode_steps=4)[0].output_ids

        eng = self._draft_engine(
            params, spec_k=3, enable_prefix_cache=False,
        )
        free0 = eng.pool.n_free
        eng.submit(GenRequest(
            rid="a", input_ids=prompt, max_new_tokens=12, greedy=True,
        ))
        eng.step(decode_steps=1)
        assert eng.pool.n_free < free0          # pages held (both pools)
        parts = eng.pause()
        assert eng.pool.n_free == free0         # all released in lockstep
        got = parts[0].output_ids
        assert parts[0].finish_reason == "interrupted"
        assert 0 < len(got) < 12 and got == ref[: len(got)]
        eng.resume()
        eng.submit(GenRequest(
            rid="a2", input_ids=prompt + got,
            max_new_tokens=12 - len(got), greedy=True,
        ))
        outs = eng.run_until_done(decode_steps=4)
        assert got + outs[0].output_ids == ref
        assert eng.draft_kv_pool_bytes() > 0

    def test_draft_weight_swap_version_bump(self, params):
        """update_draft_params bumps draft_version WITHOUT touching the
        policy version (spec decode is distribution-preserving, greedy
        outputs are unchanged); update_params(draft_params=...) swaps
        both under one lock and bumps both versions."""
        eng = self._draft_engine(params, max_slots=1, spec_k=2)
        eng.submit(GenRequest(
            rid="a", input_ids=[1, 2, 3], max_new_tokens=4, greedy=True,
        ))
        o0 = eng.run_until_done(decode_steps=2)[0]
        dcfg = dataclasses.replace(CFG, n_layers=1)
        new_draft = tfm.init_params(dcfg, jax.random.key(9))
        eng.update_draft_params(new_draft)
        assert eng.draft_version == 1 and eng.version == 0
        assert len(eng.prefix) == 0
        eng.submit(GenRequest(
            rid="b", input_ids=[1, 2, 3], max_new_tokens=4, greedy=True,
        ))
        o1 = eng.run_until_done(decode_steps=2)[0]
        assert o1.output_ids == o0.output_ids   # outputs untouched
        assert o1.version == 0
        # policy + draft ride-along: one pause window, both versions move
        eng.update_params(
            tfm.init_params(CFG, jax.random.key(11)), version=3,
            draft_params=new_draft,
        )
        assert eng.version == 3 and eng.draft_version == 2

    def test_mixed_vanilla_spec_traffic_bounded_compiles(self, params, rng):
        """Toggling spec on/off on a draft-model engine (the vanilla
        chunk maintains the draft pool with a headless draft step, so
        both chunk kinds share one state pytree) must not grow jit
        specializations past the warm set."""
        eng = self._draft_engine(
            params, max_slots=4, max_seqlen=256, page_size=16, spec_k=3,
        )
        eng.spec = False

        def burst(tag, plens):
            for i, plen in enumerate(plens):
                eng.submit(GenRequest(
                    rid=f"{tag}{i}",
                    input_ids=[int(x) for x in rng.integers(1, 128, plen)],
                    max_new_tokens=6, greedy=True,
                ))
            eng.run_until_done(decode_steps=3)

        burst("v", [3, 9, 17, 33])
        eng.spec = True
        burst("s", [3, 9, 17, 33])
        eng.spec = False
        burst("v2", [5, 21])
        eng.spec = True
        warmed = eng.n_compiles()
        eng.spec = False
        burst("v3", [11, 29, 60])
        eng.spec = True
        burst("s2", [7, 45, 80])
        assert eng.n_compiles() == warmed


class TestChunkBoundarySync:
    """The dispatch-ahead flag fetch: the harvest-flag D2H copy starts at
    chunk dispatch and resolves one chunk later (pipelined mode), so
    steady-state decode issues ZERO blocking device_get calls at chunk
    boundaries — proven by trace (a counting device_get shim) plus the
    engine's own blocked-resolve counter, the same event-log proof style
    as the fwd_pipe overlap test."""

    def test_steady_state_zero_blocking_device_get(self, params, monkeypatch):
        eng = _engine(
            params, False, max_slots=2, max_seqlen=512,
            pipeline_chunks=True,
        )
        eng.submit(GenRequest(
            rid="a", input_ids=[1, 2, 3, 4, 5], max_new_tokens=400,
            greedy=True,
        ))
        eng.step(decode_steps=4)    # admit + first dispatch
        eng.step(decode_steps=4)    # warm both pipeline stages
        # pace the warm-up's in-flight chunk too: the window's first
        # resolve is of THAT chunk (CPU dispatch is asynchronous)
        jax.block_until_ready((eng.state.lens, eng._prev_flags))
        metrics_mod.counters.clear(metrics_mod.GEN_CHUNK_FLAG_FETCHES)
        metrics_mod.counters.clear(metrics_mod.GEN_CHUNK_FLAG_BLOCKED)
        calls = []
        orig = jax.device_get
        monkeypatch.setattr(
            jax, "device_get",
            lambda *a, **kw: (calls.append(a), orig(*a, **kw))[1],
        )
        n_chunks = 10
        for _ in range(n_chunks):
            eng.step(decode_steps=4)
            # harness pacing only: wait out the in-flight chunk so the
            # next resolve measures the protocol, not CPU scheduling.
            # ALL of its outputs: on jax 0.9's CPU client the outputs of
            # one execution turn ready one by one, so the state being
            # ready does not make the flag tuple ready in the same instant
            jax.block_until_ready((eng.state.lens, eng._prev_flags))
        assert calls == []          # the trace assertion: zero device_get
        assert metrics_mod.counters.get(
            metrics_mod.GEN_CHUNK_FLAG_FETCHES
        ) == n_chunks
        assert metrics_mod.counters.get(
            metrics_mod.GEN_CHUNK_FLAG_BLOCKED
        ) == 0
        # the engine still harvests correctly after the window
        monkeypatch.setattr(jax, "device_get", orig)
        outs = eng.run_until_done(decode_steps=64)
        assert outs and outs[0].finish_reason == "length"

    def test_spec_chunk_flags_prefetch_too(self, params, rng):
        """The same protocol covers spec chunks (their longer aux tuple
        rides the same dispatch-ahead copy)."""
        eng = _engine(
            params, True, max_slots=2, max_seqlen=512, spec_k=3,
            pipeline_chunks=True,
        )
        eng.submit(GenRequest(
            rid="a",
            input_ids=[int(x) for x in rng.integers(1, 128, 6)],
            max_new_tokens=200, greedy=True,
        ))
        eng.step(decode_steps=2)
        eng.step(decode_steps=2)
        jax.block_until_ready((eng.state.lens, eng._prev_flags))
        metrics_mod.counters.clear(metrics_mod.GEN_CHUNK_FLAG_BLOCKED)
        for _ in range(5):
            eng.step(decode_steps=2)
            jax.block_until_ready((eng.state.lens, eng._prev_flags))
        assert metrics_mod.counters.get(
            metrics_mod.GEN_CHUNK_FLAG_BLOCKED
        ) == 0
        assert eng.stats["spec_draft_tokens"] > 0


class TestNGramDrafter:
    def test_bigram_match_proposes_continuation(self):
        d = NGramDrafter()
        # context ... 1 2 3 4 1 2 -> bigram (1, 2) matched at 0 -> 3 4 ...
        ctx = jnp.asarray([[1, 2, 3, 4, 1, 2, 0, 0]], jnp.int32)
        lens = jnp.asarray([5], jnp.int32)   # ctx[5] = 2 is the last token
        out = d.propose(ctx, lens, jnp.asarray([99], jnp.int32), 3)
        assert out[0].tolist() == [3, 4, 1]

    def test_unigram_fallback_then_hint(self):
        d = NGramDrafter()
        # no bigram (5, 2) occurs earlier; unigram 2 at index 1 -> 3, 4...
        ctx = jnp.asarray([[1, 2, 3, 4, 5, 2, 0, 0]], jnp.int32)
        lens = jnp.asarray([5], jnp.int32)
        out = d.propose(ctx, lens, jnp.asarray([99], jnp.int32), 3)
        assert out[0].tolist() == [3, 4, 5]
        # nothing matches at all -> the greedy-from-last-logits hint
        ctx = jnp.asarray([[1, 2, 3, 4, 5, 6, 0, 0]], jnp.int32)
        out = d.propose(ctx, jnp.asarray([5], jnp.int32),
                        jnp.asarray([99], jnp.int32), 2)
        assert out[0].tolist() == [99, 99]

    def test_proposals_never_cross_valid_region(self):
        d = NGramDrafter()
        # the current pair sits at (3, 4); the only EARLIER bigram (1, 2)
        # is at (1, 2), so the continuation starts at index 3 and may read
        # up to index lens (the pending last token) — past that, proposals
        # fill with the hint, never with stale buffer garbage (the 7s)
        ctx = jnp.asarray([[0, 1, 2, 1, 2, 7, 7, 7]], jnp.int32)
        lens = jnp.asarray([4], jnp.int32)
        out = d.propose(ctx, lens, jnp.asarray([50], jnp.int32), 4)
        assert out[0].tolist() == [1, 2, 50, 50]


class TestServingSurface:
    async def test_spec_toggle_endpoint_and_metrics(self, params):
        """POST /spec_decode flips the engine between chunks; /metrics_json
        reports the spec config + realized accept rate."""
        from aiohttp.test_utils import TestClient, TestServer

        from areal_tpu.gen.server import GenerationHTTPServer

        eng = _engine(params, True, spec_k=2)
        srv = GenerationHTTPServer(eng, decode_steps=2)
        client = TestClient(TestServer(srv.app))
        await client.start_server()
        try:
            r = await client.post("/spec_decode", json={"enabled": False})
            d = await r.json()
            assert d["success"] and d["spec_decode"] is False
            assert d["spec_k"] == 2 and eng.spec is False
            r = await client.post("/spec_decode", json={"enabled": True})
            assert (await r.json())["spec_decode"] is True
            r = await client.post("/spec_decode", json={})
            assert r.status == 400
            r = await client.get("/metrics_json")
            m = await r.json()
            assert m["spec_decode"] is True and m["spec_k"] == 2
            assert "spec_accept_rate" in m
            assert "engine_spec_draft_tokens" in m
            # draft-model gauges (no draft configured on this engine)
            assert m["spec_draft_model"] is False
            assert m["draft_kv_pool_bytes"] == 0
            assert m["draft_version"] == 0
        finally:
            await client.close()


# --------------------------------------------------------------------- #
# Exhaustive spec-vs-vanilla parity sweep. Tier-1 keeps ONE representative
# configuration (matching the round-6 kernel-test policy); the rest run
# unmarked locally and on chip.
# --------------------------------------------------------------------- #

SWEEP = [
    pytest.param(1, False, 4),
    pytest.param(2, True, 3, marks=pytest.mark.slow),
    pytest.param(4, False, 1, marks=pytest.mark.slow),
    pytest.param(4, True, 6, marks=pytest.mark.slow),
    pytest.param(8, False, 2, marks=pytest.mark.slow),
]


@pytest.mark.parametrize("spec_k,pipelined,decode_steps", SWEEP)
def test_spec_parity_sweep(params, rng, spec_k, pipelined, decode_steps):
    prompts = _prompts(rng, sizes=(4, 11, 6))
    vanilla = _engine(params, False, max_slots=4)
    spec = _engine(
        params, True, max_slots=4, spec_k=spec_k, pipeline_chunks=pipelined,
    )
    for eng in (vanilla, spec):
        for i, p in enumerate(prompts):
            eng.submit(GenRequest(
                rid=f"r{i}", input_ids=p, max_new_tokens=9, greedy=True,
            ))
    o1 = {o.rid: o for o in vanilla.run_until_done(decode_steps=4)}
    o2 = {o.rid: o for o in spec.run_until_done(decode_steps=decode_steps)}
    assert set(o1) == set(o2)
    for rid in o1:
        assert o1[rid].output_ids == o2[rid].output_ids, rid
