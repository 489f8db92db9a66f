"""granitemoehybrid through the generation engine: the per-slot recurrent
state, its snapshots in the prefix cache, and what is refused.

The model, weights and tolerance are ``tests/test_granite_hybrid.py``'s;
every comparison of served log-probabilities is against the plain
token-by-token reference or against the same engine with the prefix cache
off. Requests sample at temperature 1 from one seed, so two engines that
agree on the logits (and seat the requests alike) produce the same
tokens."""

import functools

import numpy as np
import pytest

import jax

import engine_contract
from areal_tpu.base import tracing
from areal_tpu.gen.engine import GenerationEngine, GenRequest
from areal_tpu.gen.pages import PagePool, PrefixRegistry
from areal_tpu.ops.pallas import ssm_decode
from benchmark.reference import granitemoehybrid as ref
from tests.test_granite_hybrid import ARCH, CFG, TOL, seeded_params

PAGE = 8


@pytest.fixture(scope="module")
def params():
    return seeded_params(CFG)


def _engine(params, **kw):
    kw = {"max_slots": 4, "max_seqlen": 128, "max_new_tokens_cap": 48,
          "page_size": PAGE, "admit_buckets": (1, 2, 4), "seed": 3, **kw}
    return GenerationEngine(CFG, params, **kw)


@pytest.mark.parametrize("check", engine_contract.CHECKS)
def test_engine_contract(params, check):
    engine_contract.run(check, functools.partial(_engine, params), CFG, params)


def _prompt(seed, n):
    return np.random.RandomState(seed).randint(1, ARCH["vocab_size"], n).tolist()


def _run(eng, prompts, max_new=10, steps=4):
    for i, p in enumerate(prompts):
        eng.submit(GenRequest(
            rid=str(i), input_ids=list(p), max_new_tokens=max_new, temperature=1.0))
    return {o.rid: o for o in eng.run_until_done(decode_steps=steps)}


def _assert_reference(params, prompt, out):
    want = np.asarray(ref.sequence_logprobs(
        params, ARCH, list(prompt) + out.output_ids))[len(prompt) - 1:]
    np.testing.assert_allclose(out.output_logprobs, want, atol=TOL)


def test_group_through_a_snapshot_equals_the_prefix_cache_off(params):
    """A GRPO group of 6 over 4 slots (its first member prefills, the next
    three arrive in the SAME wave and are seeded from the snapshot that
    wave has just written, two more later) and a sibling that shares only
    two pages (no snapshot there: a miss for this family)."""
    base = _prompt(0, 37)
    prompts = [base] * 6 + [base[:20] + _prompt(1, 9)]
    eng = _engine(params)
    outs = _run(eng, prompts)
    cold = _run(_engine(params, enable_prefix_cache=False), prompts)
    assert eng.stats["state_snapshot_hits"] == 5
    assert eng.stats["state_snapshots_taken"] == 2
    assert eng.stats["prefix_hit_tokens"] == 5 * 32
    assert outs["6"].prefix_hit_tokens == 0 and outs["1"].prefix_hit_tokens == 32
    for rid, o in outs.items():
        assert o.output_ids == cold[rid].output_ids
        np.testing.assert_allclose(
            o.output_logprobs, cold[rid].output_logprobs, atol=TOL)
        _assert_reference(params, prompts[int(rid)], o)
    # the counters ride the chunk and admit spans and /metrics_json
    spans = tracing.spans_since(0.0)
    chunk = [s["attrs"] for s in spans if s["name"] == "gen_engine/chunk"
             and "state_slots" in s.get("attrs", {})]
    assert chunk and chunk[-1]["state_layers"] == CFG.n_ssm_layers
    assert chunk[-1]["state_bytes_per_slot"] == 4 * (8 * 8 * 16 * 4 + 3 * 96 * 4)
    admit = [s["attrs"] for s in spans if s["name"] == "gen_engine/admit"
             and s.get("attrs", {}).get("state_snapshot_hits")]
    assert admit and sum(a["state_snapshot_hits"] for a in admit) >= 5
    for key in ("state_slots", "state_snapshots_taken", "state_snapshot_hits",
                "state_snapshot_bytes", "state_snapshot_evictions"):
        assert key in eng.stats


def test_partial_hit_continues_from_the_snapshot(params):
    """A prompt that shares the snapshotted prefix and goes on for two
    more pages: seeded at the shared boundary, prefilled to its own, where
    a second snapshot is filed for ITS siblings."""
    base = _prompt(2, 17)                      # two whole pages prefilled
    longer = base[:16] + _prompt(3, 21)
    eng = _engine(params)
    _run(eng, [base])
    outs = _run(eng, [longer, longer])
    assert [outs[r].prefix_hit_tokens for r in ("0", "1")] == [16, 16]
    # (both siblings of the one wave copy their state out; the second
    # finds the node filed and its entry goes back to the free list)
    assert eng.stats["state_snapshots_taken"] == 3
    assert len(eng.prefix._snap_nodes) == 2
    for o in outs.values():
        _assert_reference(params, longer, o)
    third = _run(eng, [longer])["0"]
    assert third.prefix_hit_tokens == 32
    _assert_reference(params, longer, third)


def test_a_reused_slot_starts_from_zero_state(params):
    eng = _engine(params, max_slots=1, enable_prefix_cache=False)
    _run(eng, [_prompt(4, 40)], max_new=20)
    short = _prompt(5, 3)
    _assert_reference(params, short, _run(eng, [short])["0"])
    one = _prompt(6, 1)                       # nothing to prefill at all
    _assert_reference(params, one, _run(eng, [one])["0"])


def test_snapshots_go_under_pressure_and_with_the_weights(params):
    eng = _engine(params, state_snapshots=2)
    prompts = [_prompt(10 + i, 20) for i in range(3)]
    for p in prompts:
        _run(eng, [p], max_new=2)
    assert eng.stats["state_snapshots_taken"] == 3
    assert eng.stats["state_snapshot_evictions"] == 1
    # the first prompt's pages are still filed, its snapshot is not: a miss
    again = _run(eng, [prompts[0]], max_new=4)["0"]
    assert again.prefix_hit_tokens == 0
    _assert_reference(params, prompts[0], again)
    hit = _run(eng, [prompts[2]], max_new=4)["0"]
    assert hit.prefix_hit_tokens == 16
    eng.update_params(eng.params)
    assert len(eng.prefix) == 0 and not eng.prefix._snap_nodes
    assert len(eng.prefix._free_snaps) == 2
    after = _run(eng, [prompts[2]], max_new=4)["0"]
    assert after.prefix_hit_tokens == 0
    _assert_reference(params, prompts[2], after)


def test_registry_drops_a_snapshot_with_its_node():
    pool = PagePool(4, PAGE)
    reg = PrefixRegistry(pool, n_snapshots=2)
    ids = list(range(2 * PAGE))
    pages = pool.alloc(2)
    snap = reg.alloc_snapshot()
    reg.insert(ids, pages, snapshot=snap)
    reg.pinned.clear()
    assert reg.lookup(ids, 2) == pages and reg.hit_snapshot == snap
    # one page deep there is no snapshot: no hit for this family
    assert reg.lookup(ids[:PAGE], 1) is None
    pool.release(pages + pages)               # the slot's and the lookup's
    reg.pinned.clear()
    assert reg.evict_lru(4) == 2 and not reg._snap_nodes
    assert sorted(reg._free_snaps) == [0, 1] and reg.snapshot_evictions == 1
    # every entry pinned: none to give
    a, b = reg.alloc_snapshot(), reg.alloc_snapshot()
    reg.insert(ids, pool.alloc(2), snapshot=a)
    reg.insert(ids[::-1], pool.alloc(2), snapshot=b)
    assert reg.alloc_snapshot() is None
    reg.pinned.clear()
    assert reg.alloc_snapshot() is not None   # the least recently used


@pytest.mark.parametrize("update", ["xla", "kernel"])
def test_pause_resume_and_a_resubmitted_partial_rollout(
        params, update, monkeypatch):
    """Rebuilt by re-prefilling, the state with the pages; once with the
    update the engine picks here (XLA's on the CPU) and once with the one
    it picks on the chip (``ssm_decode_applies`` made to say yes: the
    kernel in interpret mode; two of four slots run, so its skipped rows
    are on the path)."""
    prompts = [_prompt(20, 21), _prompt(21, 13)]
    whole = _run(_engine(params), prompts, max_new=24)
    if update == "kernel":
        monkeypatch.setattr(
            ssm_decode, "ssm_decode_applies", lambda cfg, mesh=None: True)
    eng = _engine(params)
    assert (eng._ssm_update() is not None) == (update == "kernel")
    for i, p in enumerate(prompts):
        eng.submit(GenRequest(
            rid=str(i), input_ids=p, max_new_tokens=24, temperature=1.0))
    eng.step(4), eng.step(4)
    parts = {o.rid: o for o in eng.pause()}
    assert all(o.finish_reason == "interrupted" for o in parts.values())
    eng.resume()
    for rid, o in parts.items():
        eng.submit(GenRequest(
            rid=rid, input_ids=prompts[int(rid)] + o.output_ids,
            max_new_tokens=24 - len(o.output_ids), temperature=1.0))
    rest = {o.rid: o for o in eng.run_until_done(decode_steps=4)}
    for rid, o in whole.items():
        assert parts[rid].output_ids + rest[rid].output_ids == o.output_ids
        np.testing.assert_allclose(
            parts[rid].output_logprobs + rest[rid].output_logprobs,
            o.output_logprobs, atol=TOL)


@pytest.mark.parametrize("seeded,update", [
    (False, "xla"), (True, "xla"), (True, "kernel")],
    ids=["prefilled-xla", "snapshot-xla", "snapshot-kernel"])
def test_recurrent_state_of_a_running_request_is_the_recurrences(
        params, seeded, update, monkeypatch):
    """``recurrent_state``: after a prompt prefilled in chunks (or SEEDED
    from a sibling's snapshot and prefilled from there) and ``n - 1``
    in-place updates the slot holds what the token-by-token recurrence
    holds after the same tokens, head by head as ``[Ls, H, P, N]`` whatever
    layout the slots keep, to float32's rounding (1e-5 of a head's norm:
    the chunked scan sums in another order); the reference with its state
    rounded to bfloat16 after every token is a hundred times that away, so
    this comparison tells a 16-bit state where the log-probabilities do
    not. Once with the kernel the engine picks on the chip (interpret
    mode), which updates the stored layout in place."""
    prompt = _prompt(30, 45)
    if update == "kernel":
        monkeypatch.setattr(
            ssm_decode, "ssm_decode_applies", lambda cfg, mesh=None: True)
    eng = _engine(params)
    if seeded:
        _run(eng, [prompt], max_new=4)
    eng.submit(GenRequest(
        rid="a", input_ids=prompt, max_new_tokens=40, temperature=1.0))
    for _ in range(4):
        eng.step(4)
    assert eng.stats["state_snapshot_hits"] == int(seeded)
    n, got = eng.recurrent_state("a")
    toks = eng.partial_outputs()["a"][0]
    assert n == len(toks) and n >= 8
    fed = (prompt + toks)[:-1]
    want = ref.recurrent_state(params, ARCH, fed, "float32", len(fed))
    rounded = ref.recurrent_state(
        params, dict(ARCH, control_state_dtype="bfloat16"), fed, "float32",
        len(fed))

    def worst_head(a):
        return (np.sqrt(((a - want) ** 2).sum((-2, -1)))
                / np.sqrt((want ** 2).sum((-2, -1)))).max()

    assert got.shape == want.shape == (CFG.n_ssm_layers, 8, 8, 16)
    assert worst_head(got) < 1e-5
    assert worst_head(rounded) > 1e-3
    assert eng.recurrent_state("nobody") is None
    eng.run_until_done(decode_steps=4)
    assert eng.recurrent_state("a") is None     # holds no slot any more


@pytest.mark.parametrize("kw", [
    {"kv_dtype": "int8"}, {"mesh": "2"},
], ids=lambda kw: next(iter(kw)))
def test_engine_refuses_what_has_no_test_beside_recurrent_state(params, kw):
    if "mesh" in kw:
        from jax.sharding import Mesh
        kw = {"mesh": Mesh(np.asarray(jax.devices()[:2]), ("model",))}
    with pytest.raises(NotImplementedError, match="state-space"):
        _engine(params, **kw)


def test_trainer_recomputes_the_rollout_and_takes_a_step(params):
    """What the engine served (chunked admission + in-place decode
    updates) and what the PPO actor's inference pass recomputes on the
    packed batch (the trainer's jitted ``forward_packed``: the chunked scan
    with resets at each sequence's start) are the same numbers; and a
    training step differentiates through the scan to finite gradients
    that move the weights."""
    from areal_tpu.api.data import MicroBatchSpec, SequenceSample
    from areal_tpu.api.model import PPOHyperparameters
    from areal_tpu.interfaces.ppo import PPOActorInterface
    from areal_tpu.parallel.mesh import ParallelConfig
    from areal_tpu.train.engine import OptimizerConfig, TrainEngine

    prompts = {f"r{i}": _prompt(30 + i, n) for i, n in enumerate((6, 19, 4))}
    eng = _engine(params)
    for rid, p in prompts.items():
        eng.submit(GenRequest(
            rid=rid, input_ids=p, max_new_tokens=9, temperature=1.0))
    outs = {o.rid: o for o in eng.run_until_done(4)}
    seqs = [np.asarray(p + list(outs[rid].output_ids))
            for rid, p in prompts.items()]
    lens = [len(s) for s in seqs]
    behav = np.concatenate([
        np.r_[np.zeros(len(p) - 1), outs[rid].output_logprobs, 0.0]
        for rid, p in prompts.items()]).astype(np.float32)
    sample = SequenceSample.from_default(
        seqlens=lens, ids=list(range(len(seqs))),
        data={
            "packed_input_ids": np.concatenate(seqs).astype(np.int32),
            "packed_logprobs": behav,
            "prompt_mask": np.concatenate([
                np.r_[np.ones(len(p), bool), np.zeros(n - len(p), bool)]
                for n, p in zip(lens, prompts.values())]),
            "rewards": np.asarray([1.0, -1.0, 0.5], np.float32),
            "seq_no_eos_mask": np.zeros(len(seqs), bool),
        },
    )
    train = TrainEngine(CFG, ParallelConfig(), OptimizerConfig(lr=1e-3))
    train.load_params(jax.tree.map(np.asarray, params))
    actor = PPOActorInterface(hp=PPOHyperparameters(disable_value=True))
    got = np.asarray(
        actor.inference(train, sample, MicroBatchSpec()).data["prox_logp"])
    at = 0
    for (rid, p), s in zip(prompts.items(), seqs):
        np.testing.assert_allclose(
            got[at + len(p) - 1: at + len(s) - 1], outs[rid].output_logprobs,
            atol=TOL, err_msg=rid)
        at += len(s)
    train.setup_optimizer(10)
    before = jax.tree.map(np.asarray, train.params)
    for _ in range(2):      # (the first step's learning rate is warm-up's 0)
        stats = actor.train_step(train, sample, MicroBatchSpec())
    assert all(np.isfinite(v) for v in stats.values())
    moved = jax.tree.map(
        lambda a, b: float(np.abs(np.asarray(a) - b).max()),
        train.params, before)
    assert moved["ssm_layers"]["ssm"]["A_log"] > 0
    assert moved["ssm_layers"]["ssm"]["w_xbc"] > 0
    assert moved["layers"]["attn"]["wq"] > 0
