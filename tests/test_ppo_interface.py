"""PPO actor/critic interface smoke + semantics tests on the CPU mesh.

Counterpart of the reference's ``tests/interfaces`` PPO tests: run the full
inference → prepare (GAE) → minibatched train_step path on tiny models.
"""

import numpy as np
import pytest

from areal_tpu.api.data import MicroBatchSpec, SequenceSample
from areal_tpu.api.model import PPOHyperparameters, make_interface
from areal_tpu.base import tracing
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


def _reference_prepass(sample, hp, kl_coef):
    """The advantage pre-pass in plain NumPy, a sequence at a time: KL-shaped
    rewards, the score at the last action, GAE as a reverse loop with the
    truncation bootstrap, then the normalisation. Arrays in the sample's
    flat order, and the masked mean of the reference KL."""
    d = sample.data
    lens = [n for inner in sample.seqlens["packed_input_ids"] for n in inner]
    item_of_seq = [
        i for i, inner in enumerate(sample.seqlens["packed_input_ids"])
        for _ in inner
    ]
    offs = np.cumsum([0] + lens)
    total = int(offs[-1])
    use_values = "values" in d and not hp.disable_value
    behav = d["packed_logprobs"].astype(np.float32)
    ref = d.get("packed_ref_logprobs", behav).astype(np.float32)
    adv = np.zeros(total, np.float32)
    ret = np.zeros(total, np.float32)
    kl_rw = np.zeros(total, np.float32)
    mask = np.zeros(total, bool)
    for s, n in enumerate(lens):
        o = int(offs[s])
        plen = int(d["prompt_mask"][o:o + n].sum())
        acts = np.arange(o + plen - 1, o + n - 1)
        mask[acts] = True
        v = (d["values"][o:o + n].astype(np.float32) if use_values
             else np.zeros(n, np.float32))
        score = np.float32(d["rewards"][s]) * hp.reward_output_scaling
        score = np.clip(
            score + hp.reward_output_bias,
            -hp.max_reward_clip, hp.max_reward_clip)
        no_eos = bool(d["seq_no_eos_mask"][s]) if "seq_no_eos_mask" in d else False
        if hp.mask_no_eos_with_zero and no_eos:
            score = 0.0
        kl_rw[acts] = -np.float32(kl_coef) * (behav[acts] - ref[acts])
        last = np.float32(0.0)
        for t in acts[::-1]:
            r = kl_rw[t] + (score if t == acts[-1] else 0.0)
            if t < acts[-1]:
                nv = v[t + 1 - o]
            else:   # a truncated sequence bootstraps from the next value
                nv = v[t + 1 - o] if no_eos else 0.0
            delta = r + hp.discount * nv - v[t - o]
            last = np.float32(delta + hp.discount * hp.gae_lambda * last)
            adv[t] = last
            ret[t] = last + v[t - o]
    ref_kl_mean = float((behav - ref)[mask].mean())
    if hp.group_adv_norm:
        item = np.repeat(item_of_seq, lens)
        for g in set(item_of_seq):
            sel = mask & (item == g)
            c = adv[sel] - adv[sel].mean()
            adv[sel] = c / np.sqrt((c ** 2).mean() + 1e-5)
    elif hp.adv_norm:
        c = adv[mask] - adv[mask].mean()
        adv[mask] = c / np.sqrt((c ** 2).mean() + 1e-5)
    return adv, ret, kl_rw, ref_kl_mean


PREPASS_CASES = {
    "values": dict(
        hp=dict(max_reward_clip=0.5, reward_output_scaling=2.0,
                reward_output_bias=0.1),
        values=True),
    "disable_value": dict(hp=dict(disable_value=True), values=True),
    "adv_norm": dict(hp=dict(adv_norm=True), values=True),
    "group_adv_norm": dict(
        hp=dict(disable_value=True, group_adv_norm=True, group_size=2),
        n_items=2, group=2),
    "truncated_bootstrap": dict(
        hp=dict(mask_no_eos_with_zero=True), values=True, truncate=1),
    "kl_rewards": dict(hp=dict(kl_ctl=0.1), values=True, ref_scale=0.7),
}


@pytest.mark.parametrize("case", PREPASS_CASES)
def test_prepass_equals_numpy_reference(rng, case):
    """The compiled pre-pass against a per-sequence NumPy loop written here:
    the three attached keys and the reference KL the controller is fed."""
    c = PREPASS_CASES[case]
    hp = PPOHyperparameters(**{
        "adv_norm": False, "discount": 0.95, "gae_lambda": 0.9, "kl_ctl": 0.0,
        **c["hp"]})
    actor = make_interface("ppo_actor", hp=hp)
    sample = _rollout_sample(
        rng, n_items=c.get("n_items", 4), group=c.get("group", 1))
    n_tok = sample.data["packed_input_ids"].shape[0]
    if "ref_scale" in c:
        sample.data["packed_ref_logprobs"] = (
            sample.data["packed_logprobs"] * c["ref_scale"]
            + rng.normal(size=n_tok).astype(np.float32) * 0.05)
    if "truncate" in c:
        sample.data["seq_no_eos_mask"][c["truncate"]] = True
    if c.get("values"):
        sample.update_(SequenceSample(
            keys={"values"}, ids=list(sample.ids),
            seqlens={"values": sample.seqlens["packed_input_ids"]},
            data={"values": rng.normal(size=n_tok).astype(np.float32)}))
    want = _reference_prepass(sample, hp, actor.kl_ctl.value)
    actor._prepare(sample)
    for key, ref in zip(("advantages", "returns", "kl_rewards"), want):
        assert sample.data[key].dtype == np.float32
        np.testing.assert_allclose(
            sample.data[key], ref, rtol=1e-5, atol=1e-6, err_msg=key)
    np.testing.assert_allclose(actor._last_ref_kl, want[3], rtol=1e-5, atol=1e-7)
    if case == "truncated_bootstrap":
        # the bootstrap is what moved: the same batch not truncated differs
        sample.data["seq_no_eos_mask"][:] = False
        assert not np.allclose(
            _reference_prepass(sample, hp, 0.0)[0], want[0], rtol=1e-3)


def _prepare_compiles():
    """Programs built under each ``ppo/prepare`` span, as the compile
    listener stamped them (no stamp: none built)."""
    return [s["attrs"].get("compiled", 0) for s in tracing.drain()
            if s["name"] == "ppo/prepare"]


def test_prepass_compiles_once_per_shape_never_per_value(engines, rng):
    """One program per padded length and set of keys: not one per value of
    the adaptive KL coefficient, per batch content or per batch size."""
    actor_eng, _ = engines
    spec = MicroBatchSpec(max_tokens_per_mb=128)
    actor = make_interface("ppo_actor", hp=PPOHyperparameters(
        ppo_n_minibatches=1, use_adaptive_kl=True, kl_ctl=0.1,
        use_decoupled_loss=False, recompute_logprob=False))
    _prepare_compiles()
    coefs = []
    for _ in range(3):   # equal padded T (128), other contents, other kl_ctl
        coefs.append(actor.kl_ctl.value)
        actor.train_step(actor_eng, _rollout_sample(rng, n_items=4), spec)
    assert len(set(coefs)) == 3
    assert _prepare_compiles() == [1, 0, 0]
    longer = _rollout_sample(rng, n_items=40)   # 200..400 tokens: another T
    assert longer.data["packed_input_ids"].shape[0] > 128
    actor._prepare(longer)
    actor._prepare(_rollout_sample(rng, n_items=4))
    assert _prepare_compiles() == [1, 0]

    grpo = make_interface("ppo_actor", hp=PPOHyperparameters(
        disable_value=True, group_adv_norm=True, adv_norm=False, group_size=2))
    for n_items in (3, 5):   # batch sizes 3 and 5, both padded to T = 128
        grpo._prepare(_rollout_sample(rng, n_items=n_items, group=2))
    assert _prepare_compiles() == [1, 0]


@pytest.mark.parametrize("role", ["actor", "critic"])
def test_one_prepare_span_per_train_step(engines, rng, role):
    """The advantage pre-pass runs under ``ppo/prepare``, once per
    ``train_step``, inside the interface's own ``ppo/train_step`` span
    (the critic reaches it through the actor helper)."""
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
    # a new interface's first batch builds its pre-pass: the listener's stamp
    assert 0 < prep["attrs"].pop("compile_s") <= prep["dur_s"]
    assert prep["attrs"] == {
        "n_seqs": 4,
        "n_tokens": sum(sum(l) for l in sample.seqlens["packed_input_ids"]),
        "compiled": 1,
    }
    (built,) = [s for s in spans if s["name"] == "compile/program"
                and s["parent_id"] == prep["span_id"]]
    assert built["attrs"]["fun_name"] == "jit(prepass)"
    assert step["attrs"]["n_mbs"] == 2
    # the packer and the step's dispatch lie inside the train step's span
    # (on the packer thread when the prefetcher runs: then not as children)
    inside = [s for s in spans if s["name"] in (
        "train_pipe/pack", "train_pipe/put", "train_pipe/dispatch")]
    assert len(inside) == 3 * 2
    assert all(step["t0"] <= s["t0"] <= step["t0"] + step["dur_s"]
               for s in inside)
