"""PPO actor/critic interface smoke + semantics tests on the CPU mesh.

Counterpart of the reference's ``tests/interfaces`` PPO tests: run the full
inference → prepare (GAE) → minibatched train_step path on tiny models.
"""

import numpy as np
import pytest

from areal_tpu.api.data import MicroBatchSpec, SequenceSample
from areal_tpu.api.model import PPOHyperparameters, make_interface
from areal_tpu.models.config import ModelConfig
from areal_tpu.parallel.mesh import ParallelConfig
from areal_tpu.train.engine import OptimizerConfig, TrainEngine

ACTOR_CFG = ModelConfig(
    n_layers=2, n_q_heads=4, n_kv_heads=2, head_dim=8, hidden_dim=32,
    intermediate_dim=64, vocab_size=128, dtype="float32",
)
CRITIC_CFG = ModelConfig(
    n_layers=2, n_q_heads=4, n_kv_heads=2, head_dim=8, hidden_dim=32,
    intermediate_dim=64, vocab_size=128, dtype="float32", is_critic=True,
)


def _rollout_sample(rng, n_items=4, group=1):
    """Fake rollout output: grouped sequences with prompt masks, behavior
    logprobs (token-aligned), scalar rewards per sequence."""
    ids = list(range(n_items))
    seqlens, data_ids, pmask, lps, rewards, noeos = [], [], [], [], [], []
    for _ in range(n_items):
        inner = []
        for _ in range(group):
            plen = int(rng.integers(2, 4))
            glen = int(rng.integers(3, 8))
            n = plen + glen
            inner.append(n)
            data_ids.append(rng.integers(0, 128, size=n).astype(np.int64))
            pmask.append(np.r_[np.ones(plen, bool), np.zeros(glen, bool)])
            lp = np.zeros(n, np.float32)
            lp[plen - 1 : n - 1] = rng.normal(size=glen) * 0.1 - 1.0
            lps.append(lp)
            rewards.append(float(rng.normal()))
            noeos.append(False)
        seqlens.append(inner)
    return SequenceSample(
        keys={"packed_input_ids", "prompt_mask", "packed_logprobs",
              "packed_ref_logprobs", "rewards", "seq_no_eos_mask"},
        ids=ids,
        seqlens={
            "packed_input_ids": seqlens,
            "prompt_mask": seqlens,
            "packed_logprobs": seqlens,
            "packed_ref_logprobs": seqlens,
            "rewards": [[1] * group for _ in range(n_items)],
            "seq_no_eos_mask": [[1] * group for _ in range(n_items)],
        },
        data={
            "packed_input_ids": np.concatenate(data_ids),
            "prompt_mask": np.concatenate(pmask),
            "packed_logprobs": np.concatenate(lps),
            "packed_ref_logprobs": np.concatenate(lps) * 0.9,
            "rewards": np.array(rewards, np.float32),
            "seq_no_eos_mask": np.array(noeos),
        },
    )


@pytest.fixture(scope="module")
def engines():
    par = ParallelConfig(data=2, fsdp=1, model=2)
    actor = TrainEngine(ACTOR_CFG, par, OptimizerConfig(lr=1e-4))
    actor.init_random(0).setup_optimizer(100)
    critic = TrainEngine(CRITIC_CFG, par, OptimizerConfig(lr=1e-4))
    critic.init_random(1).setup_optimizer(100)
    return actor, critic


def test_full_ppo_round(engines, rng):
    actor_eng, critic_eng = engines
    hp = PPOHyperparameters(ppo_n_minibatches=2, use_decoupled_loss=True)
    actor = make_interface("ppo_actor", hp=hp)
    critic = make_interface("ppo_critic", hp=hp)
    sample = _rollout_sample(rng, n_items=4)
    spec = MicroBatchSpec(max_tokens_per_mb=128)

    # critic_inf -> values; actor_inf -> prox_logp (like the MFC graph)
    values = critic.inference(critic_eng, sample, spec)
    sample.update_(values)
    prox = actor.inference(actor_eng, sample, spec)
    sample.update_(prox)
    assert sample.data["values"].shape == sample.data["packed_input_ids"].shape
    assert sample.data["prox_logp"].shape == sample.data["packed_input_ids"].shape

    v0 = actor_eng.version
    stats = actor.train_step(actor_eng, sample, spec)
    assert actor_eng.version == v0 + 1
    for k in ("actor_loss", "importance_weight", "actor_clip_ratio", "approx_kl"):
        assert np.isfinite(stats[k]), (k, stats)
    # advantages were attached by _prepare and are finite
    assert np.isfinite(sample.data["advantages"]).all()
    assert sample.data["advantages"].shape == sample.data["packed_input_ids"].shape

    cstats = critic.train_step(critic_eng, sample, spec)
    assert np.isfinite(cstats["critic_loss"])


def test_grpo_critic_free(engines, rng):
    actor_eng, _ = engines
    hp = PPOHyperparameters(
        ppo_n_minibatches=1, disable_value=True, group_adv_norm=True,
        adv_norm=False, group_size=2, use_decoupled_loss=False,
        recompute_logprob=False,
    )
    actor = make_interface("ppo_actor", hp=hp)
    sample = _rollout_sample(rng, n_items=3, group=2)
    stats = actor.train_step(actor_eng, sample, MicroBatchSpec(max_tokens_per_mb=128))
    assert np.isfinite(stats["actor_loss"])
    # group normalization: per-item advantage mean ~ 0 over action tokens
    adv = sample.data["advantages"]
    pm = sample.data["prompt_mask"]
    offsets = np.cumsum(
        [0] + [sum(l) for l in sample.seqlens["packed_input_ids"]]
    )
    for i in range(sample.bs):
        seg = slice(offsets[i], offsets[i + 1])
        sel = adv[seg][~pm[seg]]
        # last token of each sequence has no action; approximate check
        assert abs(sel[np.nonzero(sel)].mean()) < 0.7


def test_advantages_match_manual_gae(engines, rng):
    """Critic-free, no normalization: advantages should equal the discounted
    reward-to-go of the KL-shaped rewards (values = 0)."""
    actor_eng, _ = engines
    hp = PPOHyperparameters(
        ppo_n_minibatches=1, disable_value=True, adv_norm=False,
        use_decoupled_loss=False, recompute_logprob=False,
        kl_ctl=0.0, discount=0.9, gae_lambda=0.8,
    )
    actor = make_interface("ppo_actor", hp=hp)
    sample = _rollout_sample(rng, n_items=2)
    actor.train_step(actor_eng, sample, MicroBatchSpec(max_tokens_per_mb=128))
    adv = sample.data["advantages"]
    pm = sample.data["prompt_mask"]
    rew = sample.data["rewards"]
    offsets = np.cumsum([0] + [sum(l) for l in sample.seqlens["packed_input_ids"]])
    for i in range(sample.bs):
        seg = slice(offsets[i], offsets[i + 1])
        a = adv[seg]
        mask = ~pm[seg]
        # action positions: prompt_len-1 .. n-2
        plen = int(pm[seg].sum())
        n = offsets[i + 1] - offsets[i]
        acts = np.arange(plen - 1, n - 1)
        # reward only at last action; values zero -> A_t = (g*l)^(k) * r
        r = np.clip(rew[i], -hp.max_reward_clip, hp.max_reward_clip)
        gl = hp.discount * hp.gae_lambda
        expected = r * gl ** (acts[-1] - acts)
        np.testing.assert_allclose(a[acts], expected, rtol=1e-4, atol=1e-5)


@pytest.mark.parametrize("role", ["actor", "critic"])
def test_one_prepare_span_per_train_step(engines, rng, role):
    """The advantage pre-pass runs under ``ppo/prepare``, once per
    ``train_step``, inside the interface's own ``ppo/train_step`` span
    (the critic reaches it through the actor helper)."""
    from areal_tpu.base import tracing

    eng = engines[0 if role == "actor" else 1]
    hp = PPOHyperparameters(
        ppo_n_minibatches=2, use_decoupled_loss=False, recompute_logprob=False)
    iface = make_interface(f"ppo_{role}", hp=hp)
    sample = _rollout_sample(rng, n_items=4)
    spec = MicroBatchSpec(max_tokens_per_mb=128)
    tracing.drain()
    if role == "critic":
        sample.update_(iface.inference(eng, sample, spec))
        (inf,) = [s for s in tracing.drain() if s["name"] == "ppo/inference"]
        assert inf["attrs"] == {"n_mbs": spec.n_mbs}
    iface.train_step(eng, sample, spec)
    spans = tracing.drain()
    (step,) = [s for s in spans if s["name"] == "ppo/train_step"]
    (prep,) = [s for s in spans if s["name"] == "ppo/prepare"]
    assert prep["parent_id"] == step["span_id"]
    assert prep["attrs"] == {
        "n_seqs": 4,
        "n_tokens": sum(sum(l) for l in sample.seqlens["packed_input_ids"]),
    }
    assert step["attrs"]["n_mbs"] == 2
    # the packer and the step's dispatch lie inside the train step's span
    # (on the packer thread when the prefetcher runs: then not as children)
    inside = [s for s in spans if s["name"] in (
        "train_pipe/pack", "train_pipe/put", "train_pipe/dispatch")]
    assert len(inside) == 3 * 2
    assert all(step["t0"] <= s["t0"] <= step["t0"] + step["dur_s"]
               for s in inside)
