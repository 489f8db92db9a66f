"""solar_open2 through the generation engine: delta-rule layers' per-slot
state beside the ONE attention layer's pages, a GRPO group seeded from a
snapshot of that state, the routing record and the census of an
expert-parallel rank's share in every block.

The model, weights and tolerance are ``tests/test_solar_open2.py``'s. What
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
from areal_tpu.models import transformer as tfm
from benchmark.reference import solar_open2 as ref
from tests.test_solar_open2 import ARCH, CFG, HELD, TOL, seeded_params

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
    """Paged prefill in chunks of a page (the chunked delta rule continues
    a slot's state a piece at a time), then decode chunks: a group of four
    over four slots (the first member prefills, its siblings are seeded
    from the snapshot of the three delta-rule layers' state that wave has
    just written, and share the attention layer's pages by pointer) and
    one prompt of its own, every request's routing recorded."""
    base = _prompt(0, 37)
    prompts = [base] * 4 + [_prompt(1, 21)]
    eng = _engine(params, record_routing=True)
    assert eng._stateful and eng.n_snapshots == 8
    assert eng.state.ssm.s.shape == (3, 4, 4, 16, 16)
    assert tfm.row_state_bytes(CFG) == 3 * (4 * 16 * 16 * 4 + 3 * 192 * 4)
    outs = _run(eng, prompts)
    assert eng.stats["state_snapshot_hits"] == 3
    assert eng.stats["state_snapshots_taken"] >= 1
    assert eng.stats["prefix_hit_tokens"] == 3 * 32
    for rid, o in outs.items():
        _assert_reference(params, prompts[int(rid)], o)
        # [generated, layers, experts a token], of all 16 scored
        routing = np.asarray(o.output_routing)
        assert routing.shape == (8, 4, 4) and routing.max() >= HELD
        toks = prompts[int(rid)] + o.output_ids
        own = ref.routing(params, ARCH, toks, "float32", len(toks))
        took = routing.transpose(1, 0, 2)
        at = slice(len(toks) - 9, len(toks) - 1)
        assert (np.sort(own[:, at], -1) == np.sort(took, -1)).mean() > 0.95
    # the share's census: the running rows' pairs, those on rank 1's two
    # experts, and the held experts hit (at most 2 a layer-step)
    st = eng.stats
    assert 0 < st["moe_pairs_held"] < st["moe_pairs"]
    assert st["moe_pairs"] % (4 * 4) == 0
    assert 0 < st["moe_held_experts_hit"] <= st["moe_expert_slots"] // 8


def test_a_group_seeded_from_a_snapshot_is_the_group_without_the_cache(
        params):
    """The same group, the same seed: with the prefix cache (pages by
    pointer, the delta-rule state by a copy of the snapshot) and without it
    (every member prefills its whole prompt) the engine hands out the same
    tokens and, within the forwards' rounding, the same log-probs."""
    base = _prompt(4, 43)
    prompts = [base] * 4
    with_cache = _engine(params)
    outs = _run(with_cache, prompts)
    without = _engine(params, enable_prefix_cache=False)
    assert without.n_snapshots == 0 and without.state.snaps is None
    plain = _run(without, prompts)
    assert with_cache.stats["state_snapshot_hits"] == 3
    assert without.stats["state_snapshot_hits"] == 0
    assert without.stats["prefix_hit_tokens"] == 0
    for rid, o in outs.items():
        assert o.output_ids == plain[rid].output_ids
        np.testing.assert_allclose(
            o.output_logprobs, plain[rid].output_logprobs, atol=2 * TOL)


def test_the_running_state_and_routing_can_be_read_from_outside(params):
    """What the benchmark's state check reads of a RUNNING request: its
    delta-rule state head by head, which is the reference's after the same
    tokens, and the routing of what it has generated so far."""
    prompt = _prompt(5, 19)
    eng = _engine(params, record_routing=True)
    eng.submit(GenRequest(rid="r", input_ids=prompt, max_new_tokens=40,
                          temperature=1.0))
    eng.step(4)
    eng.step(4)
    (toks, _), = eng.partial_outputs().values()
    n, state = eng.recurrent_state("r")
    routing = eng.partial_routing("r")
    assert state.shape == (3, 4, 16, 16) and routing.shape == (n, 4, 4)
    assert eng.partial_routing("nobody") is None
    seen = (prompt + toks[:n])[:-1]
    want = ref.recurrent_state(params, ARCH, seen, "float32", 0)
    np.testing.assert_allclose(state, want, atol=TOL)
    assert _engine(params).partial_routing("r") is None    # no record kept


def test_routed_experts_on_the_einsums_and_on_the_kernel_serve_the_same(
        params, check_moe_grouped_serves_the_same):
    prompts = [_prompt(7, 19), _prompt(8, 11)]
    check_moe_grouped_serves_the_same(lambda: _engine(params), prompts)


def test_rehearsal_of_the_cell_and_selfcheck():
    """The cell end to end at the tiny preset, through the benchmark's own
    command: exit code 3, ``correct`` in float32 with every one of the six
    controls refused even at this size, a traced run's readers that need no
    device; then the yardstick's own check of BENCHMARK.json against the
    files it names (the new configuration, traffic mix, driver and five
    readers among them)."""
    import json
    import os
    import subprocess
    import sys

    from benchmark.drivers import rollout_kda_inproc as drv

    root = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    proc = subprocess.run(
        [sys.executable, "-m", "benchmark.run", "--workload",
         "solar-open2-l4.rollout_out8k", "--seed", "3000000019", "--seconds",
         "3", "--trace", "1", "--rehearse"],
        cwd=root, env=env, capture_output=True, text=True, timeout=900)
    assert proc.returncode == 3, proc.stderr[-2000:]
    lines = proc.stdout.strip().split("\n")
    last, info = json.loads(lines[-1]), json.loads(lines[-2])["info"]
    assert last["correct"] and last["failed"] == 0, info["check"]
    check = info["check"]
    assert check["checked_prefix_hits"] >= 1
    assert len(drv._STAND_INS) == 5
    assert not any(check[name]["correct"] for name in drv._STAND_INS)
    state = check["state"]
    assert state["given_the_programs_routing"]
    # every delta-rule layer of the probed request, the worst one held
    assert state["layers"] == len(state["layer_rel_diffs"]) == 3
    assert state["worst_head_rel_diff"] == max(state["layer_rel_diffs"])
    assert state["worst_head_rel_diff"] < state["rel_diff_limit"] < min(
        state["control_rel_diffs"].values())
    # what holds the state's dtype: a float32 state's entries are not
    # bfloat16's, a rounded state's all are
    assert state["control_state_rounded_not_representable"] == 0.0
    assert state["not_representable"] > state["not_representable_min"]
    assert drv._state_verdict(state, {"control_state_dtype": "bfloat16"}) == (
        True, None)
    assert not drv._state_verdict(
        dict(state, not_representable=0.0),
        {"control_state_dtype": "bfloat16"})[0]
    assert info["state_snapshot_hits"] > 0 and info["moe_pairs_held"] > 0
    assert info["pending_after_opening_population"] == 0
    assert {"gen.state_snapshot_hit_share", "moe.local_pair_share",
            "moe.experts_hit_share"} <= set(last["counts_only"])
    from benchmark import selfcheck
    from benchmark.run import load_json

    failed_before = list(selfcheck.FAILED)
    selfcheck.check_files(load_json(root, "BENCHMARK.json"))
    assert selfcheck.FAILED == failed_before
