"""nemotron_h through the generation engine: blocks of one branch over the
page pool and the per-slot recurrent state, a group seeded from a state
snapshot, the routing record and the census of an expert-parallel rank's
share.

The model, weights and tolerance are ``tests/test_nemotron_h.py``'s. What
the engine hands out is the log-probability of each SAMPLED token, so the
comparison is of those against the log-softmax of the reference's full
forward LOGITS over prompt + output (``TOL`` on both: a log-softmax of
logits within 2e-5 is within 4e-5). Requests sample at temperature 1 from
one seed."""

import functools

import jax
import numpy as np
import pytest

import engine_contract
from areal_tpu.gen.engine import GenerationEngine, GenRequest
from benchmark.reference import nemotron_h as ref
from tests.test_nemotron_h import ARCH, CFG, HELD, TOL, seeded_params

PAGE = 8


@pytest.fixture(scope="module")
def params():
    return seeded_params(CFG)


def _engine(params, **kw):
    kw = {"max_slots": 4, "max_seqlen": 128, "max_new_tokens_cap": 48,
          "page_size": PAGE, "admit_buckets": (1, 2, 4), "seed": 3, **kw}
    return GenerationEngine(CFG, params, **kw)


# the checks of the contract that a slot's STATE is behind (the others hold
# the sampler and the stop rules, which this family shares with every other:
# the suite runs near its time limit)
CONTRACT = [c for c in engine_contract.CHECKS if c in (
    "interrupt_and_resume_protocol", "continuous_batching_slot_turnover",
    "pipelined_matches_unpipelined_greedy",
    "pause_classifies_unharvested_finishes")]


@pytest.mark.parametrize("check", CONTRACT)
def test_engine_contract(params, check):
    engine_contract.run(check, functools.partial(_engine, params), CFG, params)


def _prompt(seed, n):
    return np.random.RandomState(seed).randint(1, ARCH["vocab_size"], n).tolist()


def _run(eng, prompts, max_new=8, steps=4):
    for i, p in enumerate(prompts):
        eng.submit(GenRequest(
            rid=str(i), input_ids=list(p), max_new_tokens=max_new,
            temperature=1.0))
    return {o.rid: o for o in eng.run_until_done(decode_steps=steps)}


def _assert_reference(params, prompt, out):
    toks = list(prompt) + out.output_ids
    logp = jax.nn.log_softmax(ref.sequence_logits(params, ARCH, toks), -1)
    want = np.asarray(logp)[np.arange(len(prompt) - 1, len(toks) - 1),
                            out.output_ids]
    np.testing.assert_allclose(out.output_logprobs, want, atol=2 * TOL)


def test_a_group_through_a_snapshot_matches_the_reference(params):
    """Paged prefill in chunks, then decode chunks: a group of four over
    four slots (the first member prefills, its siblings are seeded from the
    snapshot of the two Mamba-2 blocks' state that wave has just written)
    and one prompt of its own, every request's routing recorded."""
    base = _prompt(0, 37)
    prompts = [base] * 4 + [_prompt(1, 21)]
    eng = _engine(params, record_routing=True)
    outs = _run(eng, prompts)
    assert eng.stats["state_snapshot_hits"] == 3
    assert eng.stats["prefix_hit_tokens"] == 3 * 32
    for rid, o in outs.items():
        _assert_reference(params, prompts[int(rid)], o)
        # [generated, expert blocks, experts a token], of all 16 scored
        routing = np.asarray(o.output_routing)
        assert routing.shape == (8, 2, 6) and routing.max() >= HELD
        toks = prompts[int(rid)] + o.output_ids
        own = ref.routing(params, ARCH, toks, "float32", len(toks))
        took = routing.transpose(1, 0, 2)
        at = slice(len(toks) - 9, len(toks) - 1)
        assert (np.sort(own[:, at], -1) == np.sort(took, -1)).mean() > 0.95
    # the share's census: the running rows' pairs, those on rank 1's four
    # experts, and the held experts hit (at most 4 a block-step)
    st = eng.stats
    assert 0 < st["moe_pairs_held"] < st["moe_pairs"]
    assert st["moe_pairs"] % (2 * 6) == 0
    assert 0 < st["moe_held_experts_hit"] <= st["moe_expert_slots"] // 4
    assert "moe_rows" not in st


def test_routed_experts_on_the_einsums_and_on_the_kernel_serve_the_same(
        params, check_moe_grouped_serves_the_same):
    prompts = [_prompt(7, 19), _prompt(8, 11)]
    check_moe_grouped_serves_the_same(lambda: _engine(params), prompts)
