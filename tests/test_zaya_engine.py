"""ZAYA1 (family ``zaya``) through ``GenerationEngine``: the convolved
latent's CARRY per slot beside the page pool, a snapshot of it with every
run the prefix cache files, the top-1 routing census with its skip.

The tiny model and the seeded weights are ``tests/test_zaya.py``'s; the
reference is the benchmark's plain one. Pages of 8 positions. Every served
log-probability is held to the reference's full forward on prompt +
output, 1e-4 nats (float32 on both sides).
"""

import dataclasses
import functools
import json
import os
import subprocess
import sys

import numpy as np
import pytest

import jax
import jax.numpy as jnp

import engine_contract
from areal_tpu.base import tracing
from areal_tpu.gen.engine import GenerationEngine, GenRequest
from areal_tpu.models import transformer as tfm
from benchmark.drivers import rollout_cca_inproc as drv
from test_zaya import CFG, L, ROOT, TOL_NATS, _ref_logprobs, _toks, _weights
from test_zaya import ARCH as _ARCH

ARCH = dict(_ARCH, reference="zaya")

PAGE = 8


@pytest.fixture(scope="module")
def params():
    return _weights(CFG)


@pytest.fixture()
def rng():
    return np.random.default_rng(11)


def _engine(params, cfg=CFG, **kw):
    kw.setdefault("max_slots", 4)
    kw.setdefault("n_pages", 64)
    return GenerationEngine(
        cfg, params, max_seqlen=128, max_new_tokens_cap=32, page_size=PAGE,
        admit_buckets=(1, 2, 4), **kw)


def _run(eng, reqs, steps=4):
    for rid, prompt, n in reqs:
        eng.submit(GenRequest(
            rid=rid, input_ids=prompt, max_new_tokens=n, temperature=1.0))
    with jax.default_matmul_precision("highest"):
        return {o.rid: o for o in eng.run_until_done(steps)}


def _check(params, prompts, outs):
    for rid, p in prompts.items():
        o = outs[rid]
        want = _ref_logprobs(params, p + list(o.output_ids))[len(p) - 1:]
        np.testing.assert_allclose(
            np.asarray(o.output_logprobs), want, atol=TOL_NATS, err_msg=rid)


@pytest.mark.parametrize("check", engine_contract.CHECKS)
def test_engine_contract(params, check):
    engine_contract.run(check, functools.partial(_engine, params), CFG, params)


def test_a_group_is_seeded_from_the_snapshot_and_agrees(params, rng):
    """The first of a group prefills its prompt in chunks and files its
    pages AND the carry at the page boundary; the others borrow the pages,
    are seeded from the snapshot and prefill the tail alone."""
    eng = _engine(params, record_routing=True)
    carry = eng.state.ssm.carry
    assert carry.shape == (L, 4, CFG.cca_carry_dim) and eng._stateful
    assert eng.n_snapshots == 2 * 4 == eng.state.snaps.carry.shape[1]
    prompt = _toks(rng, 21)
    prompts = {"a": prompt, "b": prompt, "c": prompt,
               "d": prompt[:17] + _toks(rng, 6)}
    tracing.drain()
    outs = _run(eng, [("a", prompt, 9)])
    outs.update(_run(eng, [("b", prompt, 9), ("c", prompt, 7),
                           ("d", prompts["d"], 8)]))
    _check(params, prompts, outs)
    assert [outs[r].prefix_hit_tokens for r in "abcd"] == [0, 16, 16, 16]
    st = eng.stats
    per = L * CFG.cca_carry_dim * 4
    assert tfm.row_state_bytes(CFG) == per
    # a's run of two pages (d shares both and files nothing new)
    assert (st["state_snapshots_taken"], st["state_snapshot_hits"]) == (1, 3)
    assert st["state_snapshot_bytes"] == per * (1 + 3)
    assert st["state_snapshot_evictions"] == 0
    spans = tracing.drain()
    admits = [s["attrs"] for s in spans if s["name"] == "gen_engine/admit"
              and s["attrs"].get("admitted")]
    assert sum(a["state_snapshot_hits"] for a in admits) == 3
    chunks = [s["attrs"] for s in spans if s["name"] == "gen_engine/chunk"
              and "slots" in s["attrs"]]
    for c in chunks:
        assert c["state_slots"] == c["slots"] * c["steps"]
        assert c["state_bytes_per_slot"] == per
        assert 0 <= c["moe_skip_rows"] <= c["moe_rows"] <= (
            c["slots"] * c["steps"] * L)
        assert c["moe_expert_slots"] == c["steps"] * L * 4
    assert st["state_slots"] == sum(c["state_slots"] for c in chunks)
    # the recorded routing is the reference's, the skip (4) among it
    routed = np.concatenate([np.asarray(outs[r].output_routing) for r in "abc"])
    assert routed.shape[1:] == (L, 1) and routed.max() <= 4


@pytest.mark.parametrize("n_prompt", [16, 17, 18])
def test_a_prompt_cut_at_and_around_a_page_boundary(params, rng, n_prompt):
    """Prefilled positions (prompt - 1) of one short of, exactly and one
    past two whole pages: the snapshot stands where the page sharing
    ends, wherever the prompt does."""
    eng = _engine(params)
    prompt = _toks(rng, n_prompt)
    outs = _run(eng, [("cold", prompt, 6)])
    outs.update(_run(eng, [("hit", prompt, 6)]))
    _check(params, {"cold": prompt, "hit": prompt}, outs)
    shared = (n_prompt - 1) // PAGE * PAGE
    assert outs["hit"].prefix_hit_tokens == shared
    assert eng.stats["state_snapshot_hits"] == (1 if shared else 0)


def test_a_snapshot_dropped_for_room_is_a_miss_that_agrees(params, rng):
    """The snapshot table holds two a slot. With one slot the third run
    filed takes the least recently used one's entry; a later request of
    that run finds its pages without a carry, prefills the prompt again
    and is the reference's all the same, where the newest run's is a
    hit."""
    eng = _engine(params, max_slots=1)
    assert eng.n_snapshots == 2
    prompts = {rid: _toks(rng, 21) for rid in "abc"}
    outs = {}
    for rid, prompt in prompts.items():
        outs.update(_run(eng, [(rid, prompt, 5)]))
    st = eng.stats
    assert (st["state_snapshots_taken"], st["state_snapshot_evictions"]) == (3, 1)
    prompts.update(a2=prompts["a"], c2=prompts["c"])
    outs.update(_run(eng, [("a2", prompts["a"], 6)]))
    outs.update(_run(eng, [("c2", prompts["c"], 6)]))
    assert [outs[r].prefix_hit_tokens for r in ("a2", "c2")] == [0, 16]
    assert st["state_snapshot_hits"] == 1
    _check(params, prompts, outs)


def test_a_preempted_and_readmitted_request_agrees(params, rng):
    """``tests/test_page_policy.py``'s hand-made schedule: 12 pages, two
    requests of one page of prompt and 64 tokens, chunks of 8 steps,
    ``ADMIT_HORIZON = 0``. At the sixth chunk neither can take its seventh
    page and the older is PREEMPTED with 40 tokens; re-admitted it prefills
    what it had generated again (no snapshot stands at a preempted
    request's last position: only its prompt's page is a hit), and its
    log-probs are the reference's all the same."""
    eng = GenerationEngine(
        CFG, params, max_slots=3, max_seqlen=96, page_size=PAGE, n_pages=12,
        max_new_tokens_cap=80)
    eng.ADMIT_HORIZON = 0
    prompts = {rid: _toks(rng, 9) for rid in "AB"}
    outs = _run(eng, [(rid, p, 64) for rid, p in prompts.items()], steps=8)
    assert eng.stats["preemptions"] == 1
    # its 40 generated positions again; the prompt's page (8) is a hit on
    # what its first admission filed, seeded from that run's snapshot
    assert eng.stats["preempted_tokens_recomputed"] == 40
    assert eng.stats["state_snapshot_hits"] == 1
    _check(params, prompts, outs)
    assert all(len(o.output_ids) == 64 for o in outs.values())
    assert eng.pool.reserved == 0 and not eng._carried


def test_the_census_does_not_count_the_skip(params, rng):
    """Every row routed to the skip: no expert is hit, every routed row is
    a skipped one, and the tokens are still a model's."""
    mlp = dict(params["layers"]["mlp"])
    mlp["b_router"] = mlp["b_router"].at[:, 4].add(10.0)
    skipping = {**params, "layers": {**params["layers"], "mlp": mlp}}
    eng = _engine(skipping, record_routing=True)
    prompt = _toks(rng, 12)
    outs = _run(eng, [("s", prompt, 8)])
    _check(skipping, {"s": prompt}, outs)
    st = eng.stats
    assert st["moe_experts_hit"] == 0 and st["moe_expert_slots"] > 0
    assert st["moe_skip_rows"] == st["moe_rows"] == 8 * L
    assert (np.asarray(outs["s"].output_routing) == 4).all()
    # ... and with the router's own choices some rows skip, most do not
    eng = _engine(params)
    _run(eng, [("t", prompt, 24)])
    st = eng.stats
    assert 0 < st["moe_skip_rows"] < st["moe_rows"] == 24 * L
    assert 0 < st["moe_experts_hit"] <= st["moe_expert_slots"]


def test_what_is_not_built_is_refused(params):
    with pytest.raises(NotImplementedError, match="int8"):
        _engine(params, kv_dtype="int8")
    from jax.sharding import Mesh

    mesh = Mesh(np.asarray(jax.devices()[:2]).reshape(1, 2), ("data", "model"))
    with pytest.raises(NotImplementedError, match="mesh"):
        _engine(params, mesh=mesh)


@pytest.mark.parametrize("case", [
    "sound", "no_router_state", "low_precision", "wrong_router"])
def test_benchmark_check_and_its_stand_ins(params, rng, case):
    """The driver's verdict on a sound engine's samples (judged against
    the reference GIVEN the program's routing), and what it must refuse:
    its two stand-in programs, and a program whose router is another."""
    eng = _engine(params, record_routing=True)
    prompt = _toks(rng, 26)
    outs = _run(eng, [("a", prompt, 12)])
    outs.update(_run(eng, [("b", prompt, 14)]))
    samples = []
    for rid in "ab":
        o = outs[rid]
        forced = np.full((L, len(prompt) + len(o.output_ids)), -1, np.int32)
        forced[:, len(prompt) - 1 : -1] = np.asarray(o.output_routing)[:, :, 0].T
        samples.append({
            "tokens": prompt + list(o.output_ids), "start": len(prompt),
            "logprobs": o.output_logprobs, "forced": forced})
    chk = {"seq_mean_abs_diff_limit_nats": 1e-4, "control_dtype": "bfloat16",
           "router_agreement_min": 0.9}
    if case == "wrong_router":
        for s in samples:
            s["forced"] = np.where(s["forced"] >= 0, (s["forced"] + 1) % 5, -1)
    verdict = drv._check(params, ARCH, "float32", samples, chk)
    if case == "sound":
        assert verdict["correct"], verdict
        assert verdict["router_agreement_free_running"] == 1.0
        assert verdict["router_agreement_given_earlier_choices"] == 1.0
        assert verdict["max_abs_diff_nats"] < TOL_NATS
    elif case == "wrong_router":
        assert not verdict["correct"]
        assert verdict["router_agreement_given_earlier_choices"] < 0.5
    else:
        name = {"no_router_state": "control_no_router_state",
                "low_precision": "control"}[case]
        assert not verdict[name]["correct"], verdict[name]


def test_the_pool_after_a_prefix_hit_is_held_to_the_reference(params, rng):
    """A lost snapshot reaches two positions and no log-probability of a
    continuation: the benchmark compares what the POOL holds of the first
    position a hit computed itself. A sound engine's is the reference's;
    one whose snapshot was zeroed holds what the control computes."""
    prompt = _toks(rng, 26)
    readings = {}
    for lose in (False, True):
        eng = _engine(params)
        _run(eng, [("a", prompt, 4)])
        if lose:
            eng.state = dataclasses.replace(
                eng.state, snaps=jax.tree.map(jnp.zeros_like, eng.state.snaps))
        eng.submit(GenRequest(
            rid="b", input_ids=prompt, max_new_tokens=20, temperature=1.0))
        with jax.default_matmul_precision("highest"):
            assert eng.step(4) == []
        live = {"b": {"req": drv.traffic_gen.Request("b", prompt, 20)}}
        assert drv._probe_boundary(eng, {}) is None  # "a" holds no slot
        probe = drv._probe_boundary(eng, live)
        assert probe[0] == prompt[:25] and probe[1].shape == (L, 2, 2, 16)
        readings[lose] = drv._boundary_check(
            params, ARCH, probe, {"boundary_rel_diff_limit": 1e-3})
    sound, lost = readings[False], readings[True]
    assert sound["position"] == 24
    assert sound["rel_diff"] < 1e-5 < 0.1 < sound["control_lost_snapshot_rel_diff"]
    assert abs(lost["rel_diff"] - lost["control_lost_snapshot_rel_diff"]) < 1e-5
    # the last layer's is reported beside it: a sound engine's is the
    # reference's there too, a lost snapshot's the control's
    assert sound["last_layer_rel_diff"] < 1e-5
    assert abs(lost["last_layer_rel_diff"]
               - lost["last_layer_control_lost_snapshot_rel_diff"]) < 1e-4
    assert lost["last_layer_rel_diff"] > 0.1


def test_rehearsal_of_the_cell():
    """The cell end to end at the tiny preset, through the benchmark's own
    command: exit code 3, ``correct``, nothing compiled in the window."""
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    proc = subprocess.run(
        [sys.executable, "-m", "benchmark.run", "--workload",
         "zaya1-8b-l16.rollout_out8k", "--seed", "3000000019", "--seconds",
         "3", "--rehearse"],
        cwd=ROOT, env=env, capture_output=True, text=True, timeout=600)
    assert proc.returncode == 3, proc.stderr[-2000:]
    lines = proc.stdout.strip().split("\n")
    last, info = json.loads(lines[-1]), json.loads(lines[-2])["info"]
    assert last["correct"] and last["failed"] == 0, info["check"]
    check = info["check"]
    assert check["checked_prefix_hits"] >= 1
    assert not any(check[name]["correct"] for name in drv._CONTROLS)
    assert info["state_snapshot_hits"] > 0 and info["moe_skip_rows"] > 0
    assert info["pending_after_opening_population"] == 0
