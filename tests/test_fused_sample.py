"""Fused LM-head + sampling epilogue: exactness, distribution, and engine
composition (docs/performance.md "Fused sampling epilogue").

The load-bearing contracts:
- greedy slots are TOKEN-exact and logprob-exact (up to float
  associativity) vs the materialize-then-sample reference, at the op level
  across block sizes and through the full engine;
- temperature / top-k sampling is distribution-exact (chi-square on a
  toy vocab) — same marginal, different RNG stream;
- composition: warp-bucket fallback rows (top-p), pause/resume, tp2
  serving, bounded compiles, telemetry counters;
- the ``sample_tokens(warp=False)`` gather-then-normalize logprob fast
  path equals the full ``log_softmax`` formulation exactly.
"""

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from areal_tpu.base import metrics as metrics_mod
from areal_tpu.gen.engine import GenerationEngine, GenRequest
from areal_tpu.gen.sampling import (
    SamplingParams,
    _plain_temperature,
    sample_tokens,
)
from areal_tpu.models import transformer as tfm
from areal_tpu.models.config import ModelConfig
from areal_tpu.ops import fused_sample as fs

CFG = ModelConfig(
    n_layers=2, n_q_heads=4, n_kv_heads=2, head_dim=8, hidden_dim=32,
    intermediate_dim=64, vocab_size=128, dtype="float32",
)

# granite's kind of head in small: the embedding is the head, its logits
# divided by 8
TIED_CFG = ModelConfig(
    n_layers=2, n_q_heads=4, n_kv_heads=2, head_dim=8, hidden_dim=32,
    intermediate_dim=64, vocab_size=128, dtype="float32",
    tied_embedding=True, logits_scaling=8.0,
)

# chi-square threshold: df = 15 (16-token toy vocab), p ~ 1e-4
CHI2_CRIT = 45.0
N_DRAWS = 20000


@pytest.fixture(scope="module")
def params():
    return tfm.init_params(CFG, jax.random.key(5))


def _engine(params, fused=None, **kw):
    kw.setdefault("max_slots", 2)
    kw.setdefault("max_seqlen", 128)
    return GenerationEngine(
        CFG, params, fused_sample=fused, **kw
    )


def _prompts(rng, sizes=(5, 9, 3)):
    return [[int(x) for x in rng.integers(1, 128, size=n)] for n in sizes]


def _head_problem(R=6, E=32, V=500, seed=0, vocab_rows=False,
                  logits_scale=1.0, soft_cap=None):
    """``(x, w, logits)``: ``w`` is ``[E, V]``, or the same numbers as
    ``[V, E]`` (a tied embedding as stored); ``logits`` are the reference's,
    materialised the way ``models/transformer.py:_head`` does."""
    kx, kw = jax.random.split(jax.random.key(seed))
    x = jax.random.normal(kx, (R, E), jnp.float32)
    w = jax.random.normal(kw, (E, V), jnp.float32) * 0.3
    logits = (x @ w).astype(jnp.float32)
    if logits_scale != 1.0:
        logits = logits / logits_scale
    if soft_cap is not None:
        logits = soft_cap * jnp.tanh(logits / soft_cap)
    return x, (jnp.asarray(w.T) if vocab_rows else w), logits


# how the head reaches ``fused_sample``: the layout of the weight, the
# scaling of its logits, a soft cap (the arguments of both)
HEADS = [
    pytest.param({}, id="ev"),
    pytest.param({"vocab_rows": True}, id="ve"),
    pytest.param({"logits_scale": 8.0}, id="ev_scaled"),
    pytest.param(
        {"vocab_rows": True, "logits_scale": 8.0, "soft_cap": 0.4},
        id="ve_scaled_capped"),
]
LAYOUTS = [pytest.param({}, id="ev"),
           pytest.param({"vocab_rows": True}, id="ve")]


class TestOpParity:
    """fused_sample vs sample_tokens over materialized logits."""

    @pytest.mark.parametrize(
        "block", [100, pytest.param(64, marks=pytest.mark.slow),
                  pytest.param(500, marks=pytest.mark.slow),
                  pytest.param(512, marks=pytest.mark.slow),
                  pytest.param(7, marks=pytest.mark.slow)],
    )
    @pytest.mark.parametrize("head", HEADS)
    def test_greedy_exact_and_lp_formula(self, block, head):
        x, w, logits = _head_problem(**head)
        temp = jnp.array([0.0, 1.0, 0.7, 0.0, 1.3, 1.0], jnp.float32)
        greedy = temp <= 0.0
        R = x.shape[0]
        sp = SamplingParams(
            temperature=temp, top_p=jnp.ones((R,), jnp.float32),
            top_k=jnp.full((R,), 1 << 30, jnp.int32),
        )
        key = jax.random.key(3)
        ref_tok, ref_lp = sample_tokens(key, logits, sp, warp=False)
        out = fs.fused_sample(
            key, x, w, temp, greedy, block_size=block, use_pallas=False,
            **head,
        )
        g = np.asarray(greedy)
        # greedy rows: token- and logprob-exact
        assert np.array_equal(np.asarray(out["tokens"])[g],
                              np.asarray(ref_tok)[g])
        np.testing.assert_allclose(
            np.asarray(out["logprobs"])[g], np.asarray(ref_lp)[g], atol=1e-4
        )
        # every row: raw argmax exact; returned lp == log_softmax at the
        # sampled token w.r.t. the warped distribution
        assert np.array_equal(
            np.asarray(out["argmax"]), np.asarray(jnp.argmax(logits, -1))
        )
        warped = np.asarray(logits) / np.maximum(
            np.asarray(temp)[:, None], 1e-6
        )
        lse = np.asarray(
            jax.scipy.special.logsumexp(jnp.asarray(warped), axis=-1)
        )
        tok = np.asarray(out["tokens"])
        np.testing.assert_allclose(
            np.asarray(out["logprobs"]),
            warped[np.arange(R), tok] - lse, atol=1e-4,
        )

    def test_topk_sample_stays_in_topk_set(self):
        x, w, logits = _head_problem()
        R = x.shape[0]
        temp = jnp.ones((R,), jnp.float32)
        topk = jnp.array([1 << 30, 5, 1 << 30, 1 << 30, 3, 1 << 30],
                         jnp.int32)
        for seed in range(8):
            out = fs.fused_sample(
                jax.random.key(seed), x, w, temp,
                jnp.zeros((R,), bool), topk=topk, block_size=64,
                use_pallas=False,
            )
            tok = np.asarray(out["tokens"])
            for r in (1, 4):
                k = int(topk[r])
                top_ids = np.argsort(-np.asarray(logits)[r])[:k]
                assert tok[r] in top_ids

    @pytest.mark.parametrize("head", HEADS)
    def test_pallas_interpret_matches_xla(self, head):
        """The kernel (CPU interpret mode) agrees with the streamed XLA
        path on everything deterministic: greedy tokens, argmax, and the
        logprob formula for whatever token its own stream sampled."""
        x, w, logits = _head_problem(**head)
        temp = jnp.array([0.0, 1.0, 0.7, 0.0, 1.3, 1.0], jnp.float32)
        greedy = temp <= 0.0
        R = x.shape[0]
        out = fs.fused_sample(
            jax.random.key(3), x, w, temp, greedy, block_size=128,
            use_pallas=True, **head,
        )
        g = np.asarray(greedy)
        ref = np.asarray(jnp.argmax(logits, -1))
        assert np.array_equal(np.asarray(out["tokens"])[g], ref[g])
        assert np.array_equal(np.asarray(out["argmax"]), ref)
        warped = np.asarray(logits) / np.maximum(
            np.asarray(temp)[:, None], 1e-6
        )
        lse = np.asarray(
            jax.scipy.special.logsumexp(jnp.asarray(warped), axis=-1)
        )
        tok = np.asarray(out["tokens"])
        np.testing.assert_allclose(
            np.asarray(out["logprobs"]),
            warped[np.arange(R), tok] - lse, atol=1e-3,
        )

    def test_explicit_pallas_with_topk_or_mesh_raises(self):
        x, w, _ = _head_problem()
        R = x.shape[0]
        temp = jnp.ones((R,), jnp.float32)
        with pytest.raises(ValueError, match="top-k"):
            fs.fused_sample(
                jax.random.key(0), x, w, temp, jnp.zeros((R,), bool),
                topk=jnp.full((R,), 4, jnp.int32), use_pallas=True,
            )
        from jax.sharding import Mesh

        mesh = Mesh(np.array(jax.devices()[:2]), ("model",))
        with pytest.raises(ValueError, match="mesh"):
            fs.fused_sample(
                jax.random.key(0), x, w, temp, jnp.zeros((R,), bool),
                use_pallas=True, mesh=mesh,
            )

    def test_weight_that_does_not_match_its_layout_raises(self):
        x, w, _ = _head_problem()
        R = x.shape[0]
        with pytest.raises(ValueError, match="does not match"):
            fs.fused_sample(
                jax.random.key(0), x, w, jnp.ones((R,), jnp.float32),
                jnp.zeros((R,), bool), use_pallas=False, vocab_rows=True,
            )


class TestFusedSampleApplies:
    """``fused_sample_applies``: the engine's default, from what it can
    observe (no flag)."""

    @staticmethod
    def _params(head, embed=jnp.bfloat16, tied=False):
        """The tree as the engine serves it: a tied model has no head."""
        tree = {"embed": {"weight": jax.ShapeDtypeStruct((128, 32), embed)}}
        if not tied:
            tree["head"] = {"weight": jax.ShapeDtypeStruct((32, 128), head)}
        return tree

    @pytest.mark.parametrize(
        "case,want",
        [("tpu_one_device", True), ("mesh_of_one", True), ("cpu", False),
         ("mesh", False), ("tied_head", True), ("critic", False),
         ("small_vocab", False), ("head_in_another_dtype", False),
         ("tied_embedding_in_another_dtype", False),
         ("scaled_logits", True), ("tied_mesh", False),
         # an untied head is what the kernel reads, whatever the embedding's
         ("untied_embedding_in_another_dtype", True)],
    )
    def test_rule(self, case, want):
        import dataclasses

        from jax.sharding import Mesh

        cfg = dataclasses.replace(CFG, dtype="bfloat16")
        head = embed = jnp.bfloat16
        mesh, platform = None, "tpu"
        if case == "cpu":
            platform = "cpu"
        elif case in ("mesh", "tied_mesh"):
            mesh = Mesh(np.array(jax.devices()[:2]), ("model",))
        elif case == "mesh_of_one":
            mesh = Mesh(np.array(jax.devices()[:1]), ("model",))
        elif case == "critic":
            cfg = dataclasses.replace(cfg, is_critic=True)
        elif case == "small_vocab":
            cfg = dataclasses.replace(cfg, vocab_size=64)
        elif case == "head_in_another_dtype":
            head = jnp.float32
        elif case == "scaled_logits":
            cfg = dataclasses.replace(cfg, logits_scaling=8.0)
        elif case.endswith("embedding_in_another_dtype"):
            embed = jnp.float32
        tied = case.startswith("tied")
        cfg = dataclasses.replace(cfg, tied_embedding=tied)
        assert fs.fused_sample_applies(
            cfg, self._params(head, embed, tied), mesh=mesh,
            platform=platform) is want

    def test_platform_defaults_to_the_first_device(self):
        assert fs.fused_sample_applies(CFG, self._params(jnp.float32)) is False


class TestKernelForms:
    """The Pallas kernel (interpret mode) over row groups and vocabulary
    blocks with a masked tail."""

    @pytest.mark.parametrize(
        "R,V,block,head",
        [pytest.param(72, 700, 256, {}, id="nine_groups_of_8"),
         pytest.param(64, 1024, 512, {}, id="two_groups_of_32_no_tail"),
         pytest.param(5, 130, None, {}, id="padded_rows_block_from_shapes"),
         # [V, E] row blocks: the last block's rows past the vocabulary
         # are whatever the pipeline read there
         pytest.param(72, 700, 256, {"vocab_rows": True},
                      id="ve_nine_groups_of_8"),
         pytest.param(64, 1024, 512, {"vocab_rows": True},
                      id="ve_two_groups_of_32_no_tail"),
         pytest.param(5, 130, None, {"vocab_rows": True, "soft_cap": 0.4},
                      id="ve_padded_rows_block_from_shapes_capped"),
         # the two tied cells in small: 80 rows (five groups of 16), whole
         # blocks and a tail of ONE lane tile, logits scaled by 8
         pytest.param(80, 640, 256,
                      {"vocab_rows": True, "logits_scale": 8.0},
                      id="ve_80_rows_tail_of_one_lane_tile_scaled"),
         pytest.param(80, 640, 256, {"logits_scale": 8.0, "soft_cap": 0.4},
                      id="ev_80_rows_tail_of_one_lane_tile_scaled_capped")],
    )
    def test_greedy_exact_and_lp_formula(self, R, V, block, head):
        x, w, logits = _head_problem(R=R, E=32, V=V, seed=4, **head)
        temp = jnp.where(jnp.arange(R) % 3 == 0, 0.0, 0.8).astype(
            jnp.float32)
        greedy = temp <= 0.0
        out = fs.fused_sample(
            jax.random.key(9), x, w, temp, greedy, block_size=block,
            use_pallas=True, **head,
        )
        # greedy rows: the reference sampler's tokens and log-probs
        sp = SamplingParams(
            temperature=temp, top_p=jnp.ones((R,), jnp.float32),
            top_k=jnp.full((R,), 1 << 30, jnp.int32),
        )
        ref_tok, ref_lp = sample_tokens(
            jax.random.key(9), logits, sp, warp=False)
        np.testing.assert_array_equal(
            np.asarray(out["tokens"])[np.asarray(greedy)],
            np.asarray(ref_tok)[np.asarray(greedy)])
        np.testing.assert_allclose(
            np.asarray(out["logprobs"])[np.asarray(greedy)],
            np.asarray(ref_lp)[np.asarray(greedy)], atol=1e-3)
        g = np.asarray(greedy)
        ref = np.asarray(jnp.argmax(logits, -1))
        tok = np.asarray(out["tokens"])
        assert tok.shape == (R,) and (tok >= 0).all() and (tok < V).all()
        assert np.array_equal(tok[g], ref[g])
        assert np.array_equal(np.asarray(out["argmax"]), ref)
        warped = np.asarray(logits) / np.maximum(
            np.asarray(temp)[:, None], 1e-6)
        lse = np.asarray(
            jax.scipy.special.logsumexp(jnp.asarray(warped), axis=-1))
        np.testing.assert_allclose(np.asarray(out["norm"]), lse, rtol=1e-5)
        np.testing.assert_allclose(
            np.asarray(out["logprobs"]), warped[np.arange(R), tok] - lse,
            atol=1e-3,
        )
        # sampled rows are not all one token: the draws differ by row
        assert len(set(tok[~g].tolist())) > 1

    @pytest.mark.parametrize("head", LAYOUTS)
    def test_draws_do_not_depend_on_the_block(self, head):
        """The interpreted kernel draws from a hash of (seed, row, column):
        the same tokens whatever the block, a partial last block (500
        columns) included; the sums differ only in their order."""
        x, w, logits = _head_problem(R=16, E=32, V=500, seed=2, **head)
        R = x.shape[0]
        args = (jax.random.key(5), x, w, jnp.ones((R,), jnp.float32),
                jnp.zeros((R,), bool))
        one, other = (
            fs.fused_sample(*args, block_size=b, use_pallas=True, **head)
            for b in (128, 256))
        assert set(one) == {"tokens", "logprobs", "argmax", "norm"}
        for k in ("tokens", "argmax"):
            np.testing.assert_array_equal(
                np.asarray(one[k]), np.asarray(other[k]), err_msg=k)
        for k in ("logprobs", "norm"):
            np.testing.assert_allclose(
                np.asarray(one[k]), np.asarray(other[k]), atol=1e-5,
                err_msg=k)
        lp_all = np.asarray(jax.nn.log_softmax(logits, -1))
        np.testing.assert_allclose(
            np.asarray(one["logprobs"]),
            lp_all[np.arange(R), np.asarray(one["tokens"])], atol=1e-4)

    def test_draws_do_not_depend_on_the_layout(self):
        """One hash of (seed, row, column) whichever way the weight lies:
        a tied head draws what the same numbers draw as an untied one."""
        x, w, _ = _head_problem(R=16, E=32, V=500, seed=2)
        R = x.shape[0]
        args = (jnp.ones((R,), jnp.float32), jnp.zeros((R,), bool))
        ev = fs.fused_sample(
            jax.random.key(5), x, w, *args, block_size=128, use_pallas=True)
        ve = fs.fused_sample(
            jax.random.key(5), x, jnp.asarray(w.T), *args, block_size=128,
            use_pallas=True, vocab_rows=True)
        np.testing.assert_array_equal(
            np.asarray(ev["tokens"]), np.asarray(ve["tokens"]))
        np.testing.assert_allclose(
            np.asarray(ev["logprobs"]), np.asarray(ve["logprobs"]),
            atol=1e-5)

    def test_block_follows_from_the_shapes(self):
        from areal_tpu.ops.pallas import fused_sample as fsk

        # the rollout cells (the two tied ones last: the same bytes a
        # block whichever way the weight lies): 2048 columns; a toy
        # vocabulary: one lane tile; a head too wide for 2048 columns of
        # it: fewer
        for R, E, V in [(128, 1536, 151936), (64, 3584, 152064),
                        (64, 2048, 50304), (256, 2048, 129280),
                        (112, 2560, 151936), (80, 2048, 100352),
                        (256, 2048, 262272)]:
            assert fsk.block_columns(R, E, V, 2) == 2048
        assert fsk.block_columns(8, 32, 100, 4) == 128
        wide = fsk.block_columns(64, 16384, 152064, 2)
        assert wide % 128 == 0 and 128 <= wide < 2048

    @pytest.mark.parametrize("head", [
        pytest.param({}, id="ev"),
        pytest.param({"vocab_rows": True, "logits_scale": 8.0},
                     id="ve_scaled")])
    def test_rows_are_independent_draws_of_the_marginal(self, head):
        """256 rows that are ONE row: every call is 256 draws of one
        distribution (the kernel's uniforms differ by row and column), so
        a few calls give the chi-square of ``chip_smoke.py``'s check of the
        chip's own uniform source."""
        x1, w, logits = _head_problem(R=1, E=8, V=16, seed=1, **head)
        p = np.asarray(jax.nn.softmax(logits[0]))
        R = 256
        x = jnp.tile(x1, (R, 1))
        f = jax.jit(lambda k: fs.fused_sample(
            k, x, w, jnp.ones((R,)), jnp.zeros((R,), bool),
            use_pallas=True, **head,
        )["tokens"])
        counts = np.zeros(16)
        for k in jax.random.split(jax.random.key(11), 40):
            counts += np.bincount(np.asarray(f(k)), minlength=16)
        n = counts.sum()
        chi2 = float((((counts - n * p) ** 2) / (n * p)).sum())
        assert chi2 < CHI2_CRIT, (chi2, counts)


class TestDistribution:
    """Chi-square: the fused sampler's first-token marginal equals the
    softmax of the (warped / restricted) head output."""

    def _marginal(self, sample_fn, n=N_DRAWS, vocab=16):
        keys = jax.random.split(jax.random.key(7), n)
        toks = np.asarray(jax.vmap(sample_fn)(keys))
        return np.bincount(toks, minlength=vocab)

    def _chi2(self, counts, p):
        n = counts.sum()
        mask = p > 0
        return float(
            (((counts[mask] - n * p[mask]) ** 2) / (n * p[mask])).sum()
        )

    @pytest.mark.parametrize("head", HEADS)
    def test_temperature_marginal(self, head):
        x, w, logits = _head_problem(R=1, E=8, V=16, seed=1, **head)
        p = np.asarray(jax.nn.softmax(logits[0]))
        f = jax.jit(lambda k: fs.fused_sample(
            k, x, w, jnp.ones((1,)), jnp.zeros((1,), bool),
            block_size=7, use_pallas=False, **head,
        )["tokens"][0])
        assert self._chi2(self._marginal(f), p) < CHI2_CRIT

    def test_topk_marginal(self):
        x, w, logits = _head_problem(R=1, E=8, V=16, seed=1)
        k = 5
        lg = np.asarray(logits[0])
        keep = np.argsort(-lg)[:k]
        p = np.zeros_like(lg)
        p[keep] = np.exp(lg[keep] - lg[keep].max())
        p /= p.sum()
        f = jax.jit(lambda key: fs.fused_sample(
            key, x, w, jnp.ones((1,)), jnp.zeros((1,), bool),
            topk=jnp.full((1,), k, jnp.int32), block_size=7,
            use_pallas=False,
        )["tokens"][0])
        counts = self._marginal(f)
        assert counts[np.setdiff1d(np.arange(16), keep)].sum() == 0
        assert self._chi2(counts, p) < CHI2_CRIT

    @pytest.mark.slow
    @pytest.mark.parametrize("head", LAYOUTS)
    def test_pallas_temperature_marginal(self, head):
        x, w, logits = _head_problem(R=1, E=8, V=16, seed=1, **head)
        p = np.asarray(jax.nn.softmax(logits[0]))
        f = jax.jit(lambda k: fs.fused_sample(
            k, x, w, jnp.ones((1,)), jnp.zeros((1,), bool),
            block_size=128, use_pallas=True, **head,
        )["tokens"][0])
        assert self._chi2(self._marginal(f), p) < CHI2_CRIT


class TestLogprobFastPath:
    def test_warp_false_lp_equals_log_softmax(self):
        """The gather-then-normalize fast path in sample_tokens(warp=False)
        is EXACT vs the full log_softmax formulation (same reduction, so
        bitwise-comparable at f32 tolerance ~0)."""
        _, _, logits = _head_problem()
        R = logits.shape[0]
        temp = jnp.array([0.0, 1.0, 0.7, 0.0, 1.3, 1.0], jnp.float32)
        sp = SamplingParams(
            temperature=temp, top_p=jnp.ones((R,), jnp.float32),
            top_k=jnp.full((R,), 1 << 30, jnp.int32),
        )
        tok, lp = sample_tokens(jax.random.key(11), logits, sp, warp=False)
        warped = _plain_temperature(logits, sp)
        full = jnp.take_along_axis(
            jax.nn.log_softmax(warped, axis=-1), tok[:, None], axis=-1
        )[:, 0]
        np.testing.assert_allclose(
            np.asarray(lp), np.asarray(full), atol=1e-5
        )


class TestEngineFused:
    def test_greedy_fused_matches_reference(self, params, rng):
        """The tentpole contract: the fused epilogue's greedy decode is
        token- and logprob-exact vs the materialized reference through
        the full engine."""
        prompts = _prompts(rng)
        outs = []
        for fused in (False, True):
            eng = _engine(params, fused=fused, max_slots=4)
            for i, p in enumerate(prompts):
                eng.submit(GenRequest(
                    rid=f"r{i}", input_ids=p, max_new_tokens=10 + i,
                    greedy=True,
                ))
            outs.append({
                o.rid: o for o in eng.run_until_done(decode_steps=3)
            })
        assert set(outs[0]) == set(outs[1])
        for rid in outs[0]:
            assert outs[0][rid].output_ids == outs[1][rid].output_ids, rid
            assert outs[0][rid].finish_reason == outs[1][rid].finish_reason
            np.testing.assert_allclose(
                outs[0][rid].output_logprobs, outs[1][rid].output_logprobs,
                atol=1e-4,
            )

    def test_tied_scaled_head_greedy_fused_matches_reference(self, rng):
        """A tied, scaled head (granite's kind in small: the embedding IS
        the head, the logits divided by 8) through the engine: the fused
        epilogue reads the embedding as stored (``head_operand``) and is
        token-exact against the materialised path, whose ``_head``
        transposes and divides."""
        prompts = _prompts(rng)
        outs = []
        for fused in (False, True):
            eng = GenerationEngine(
                TIED_CFG, tfm.init_params(TIED_CFG, jax.random.key(6)),
                fused_sample=fused, max_slots=4, max_seqlen=128)
            for i, p in enumerate(prompts):
                eng.submit(GenRequest(
                    rid=f"r{i}", input_ids=p, max_new_tokens=10 + i,
                    greedy=True,
                ))
            outs.append({
                o.rid: o for o in eng.run_until_done(decode_steps=3)
            })
            assert (eng.stats["fused_rows"] > 0) == fused
            assert eng.stats["sampler_fallback_rows"] == 0
        assert set(outs[0]) == set(outs[1])
        for rid in outs[0]:
            assert outs[0][rid].output_ids == outs[1][rid].output_ids, rid
            np.testing.assert_allclose(
                outs[0][rid].output_logprobs, outs[1][rid].output_logprobs,
                atol=1e-4,
            )

    def test_tied_scaled_head_top_p_rows_take_the_fallback(self):
        """A mixed batch on the tied, scaled head: the top-p row keeps the
        sorted sampler over ITS logits row (``apply_head``, which handles
        the tie and the scaling), the others the fused pass; the greedy
        row is the materialised engine's."""

        def run(fused):
            eng = GenerationEngine(
                TIED_CFG, tfm.init_params(TIED_CFG, jax.random.key(6)),
                fused_sample=fused, max_slots=4, max_seqlen=128, seed=3)
            eng.submit(GenRequest(
                rid="g", input_ids=[5, 6, 7], max_new_tokens=8, greedy=True))
            eng.submit(GenRequest(
                rid="p", input_ids=[5, 6, 7], max_new_tokens=8,
                temperature=1.0, top_p=0.9))
            eng.submit(GenRequest(
                rid="t", input_ids=[5, 6, 7], max_new_tokens=8,
                temperature=0.8))
            outs = {o.rid: o for o in eng.run_until_done(decode_steps=2)}
            return outs, dict(eng.stats)

        got, stats = run(True)
        # one of three rows a step is the top-p one
        assert stats["sampler_fallback_rows"] > 0
        assert stats["fused_rows"] == 2 * stats["sampler_fallback_rows"]
        ref, ref_stats = run(False)
        assert ref_stats["fused_rows"] == 0
        assert got["g"].output_ids == ref["g"].output_ids
        for o in got.values():
            assert len(o.output_ids) == 8
            assert all(np.isfinite(o.output_logprobs))

    @pytest.mark.parametrize("fused", [True, False])
    def test_constructor_argument_overrides_the_rule(self, params, fused):
        """On the CPU the rule says no; the argument pins either side."""
        assert _engine(params, max_slots=1).fused is False
        eng = _engine(params, fused=fused, max_slots=1)
        assert eng.fused is fused
        eng.submit(GenRequest(
            rid="a", input_ids=[1, 2, 3], max_new_tokens=4, greedy=True,
        ))
        outs = eng.run_until_done(decode_steps=2)
        assert len(outs[0].output_ids) == 4
        assert (eng.stats["fused_rows"] > 0) == fused

    def test_mixed_batch_fallback_rows_reproducible(self, params):
        """A top-p slot routes through the sorted fallback while greedy /
        top-k / plain-temperature slots stay fused — seeded runs are
        reproducible and the greedy slot stays exact."""

        def run(fused):
            eng = _engine(params, fused=fused, max_slots=4, seed=3)
            eng.submit(GenRequest(
                rid="g", input_ids=[5, 6, 7], max_new_tokens=8,
                greedy=True,
            ))
            eng.submit(GenRequest(
                rid="p", input_ids=[5, 6, 7], max_new_tokens=8,
                temperature=1.0, top_p=0.9,
            ))
            eng.submit(GenRequest(
                rid="k", input_ids=[5, 6, 7], max_new_tokens=8,
                temperature=1.0, top_k=8,
            ))
            eng.submit(GenRequest(
                rid="t", input_ids=[5, 6, 7], max_new_tokens=8,
                temperature=0.8,
            ))
            return {o.rid: o for o in eng.run_until_done(decode_steps=2)}

        m1, m2 = run(True), run(True)
        assert {r: o.output_ids for r, o in m1.items()} == \
               {r: o.output_ids for r, o in m2.items()}
        ref = run(False)
        assert m1["g"].output_ids == ref["g"].output_ids
        for o in m1.values():
            assert len(o.output_ids) == 8
            assert all(np.isfinite(o.output_logprobs))

    def test_pause_resume_prefix_parity_fused(self, params, rng):
        """Interruption composes: a fused engine paused mid-generation
        yields a prefix of the uninterrupted chain and resubmission
        completes it exactly (the partial-rollout protocol)."""
        prompt = [int(x) for x in rng.integers(1, 128, size=5)]
        ref_eng = _engine(params, fused=True)
        ref_eng.submit(GenRequest(
            rid="ref", input_ids=prompt, max_new_tokens=12, greedy=True,
        ))
        ref = ref_eng.run_until_done(decode_steps=4)[0].output_ids
        eng = _engine(params, fused=True)
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

    def test_tp2_fused_greedy_matches_single_device(self, params, rng):
        """Fused sampling on a 2-way `model` mesh (streamed XLA epilogue
        under GSPMD, hidden states replicated before sampling) matches
        the unsharded fused engine token for token."""
        from jax.sharding import Mesh

        mesh = Mesh(np.array(jax.devices()[:2]), ("model",))
        prompts = _prompts(rng)
        eng1 = _engine(params, fused=True, max_slots=4)
        eng2 = GenerationEngine(
            CFG, params, max_slots=4, max_seqlen=128,
            fused_sample=True, mesh=mesh,
        )
        for eng in (eng1, eng2):
            for i, p in enumerate(prompts):
                eng.submit(GenRequest(
                    rid=f"r{i}", input_ids=p, max_new_tokens=8,
                    greedy=True,
                ))
        o1 = {o.rid: o for o in eng1.run_until_done(decode_steps=2)}
        o2 = {o.rid: o for o in eng2.run_until_done(decode_steps=2)}
        assert set(o1) == set(o2)
        for rid in o1:
            assert o1[rid].output_ids == o2[rid].output_ids, rid

    def test_fused_bounded_compiles_and_counters(self, params, rng):
        """Fused traffic obeys the n_compiles discipline (mixed fused
        chunks + fallback buckets add a bounded set of programs, never
        per-prompt) and ticks the fused/fallback counters."""
        metrics_mod.counters.clear(metrics_mod.GEN_FUSED_SAMPLE_STEPS)
        metrics_mod.counters.clear(metrics_mod.GEN_SAMPLER_FALLBACK_ROWS)
        eng = _engine(params, fused=True, max_slots=4, max_seqlen=256,
                      page_size=16)

        def burst(tag, plens, **req_kw):
            for i, plen in enumerate(plens):
                eng.submit(GenRequest(
                    rid=f"{tag}{i}",
                    input_ids=[int(x) for x in rng.integers(1, 128, plen)],
                    max_new_tokens=6, **req_kw,
                ))
            eng.run_until_done(decode_steps=3)

        burst("g", [3, 9, 17, 33], greedy=True)         # warm greedy
        burst("p", [3, 9], temperature=1.0, top_p=0.9)  # warm fallback
        burst("k", [5, 21], temperature=1.0, top_k=8)   # warm online top-k
        warmed = eng.n_compiles()
        burst("g2", [11, 29, 60], greedy=True)
        burst("p2", [7, 45], temperature=1.0, top_p=0.9)
        burst("k2", [13, 80], temperature=1.0, top_k=8)
        assert eng.n_compiles() == warmed
        assert metrics_mod.counters.get(
            metrics_mod.GEN_FUSED_SAMPLE_STEPS
        ) > 0
        assert metrics_mod.counters.get(
            metrics_mod.GEN_SAMPLER_FALLBACK_ROWS
        ) > 0


class TestGaugeKind:
    def test_gauge_last_value_wins_and_delta_reports_as_is(self):
        name = "test/fused_gauge"
        metrics_mod.counters.clear(name)
        base = metrics_mod.counters.snapshot()
        metrics_mod.counters.gauge(name, 4.0)
        metrics_mod.counters.gauge(name, 2.0)
        assert metrics_mod.counters.get(name) == 2.0
        assert metrics_mod.counters.kind(name) == metrics_mod.KIND_GAUGE
        d = metrics_mod.counters.delta(base)
        assert d[name] == 2.0
        metrics_mod.counters.clear(name)

    def test_telemetry_merges_gauges_with_max(self):
        from areal_tpu.system.telemetry import FleetAggregate

        agg = FleetAggregate()
        for i, v in enumerate((2.0, 4.0, 1.0)):
            agg.merge_snapshot({
                "worker": f"w{i}",
                "counters": {metrics_mod.GW_BROWNOUT_LEVEL: v},
            })
        # gauges merge via fleet max (the conservative view when workers
        # move at different times), not via sum
        assert agg.counters[metrics_mod.GW_BROWNOUT_LEVEL] == 4.0
        assert agg.kinds[metrics_mod.GW_BROWNOUT_LEVEL] == \
            metrics_mod.KIND_GAUGE
