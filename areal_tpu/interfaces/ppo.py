"""PPO actor & critic interfaces (decoupled async PPO).

TPU-native counterpart of ``realhf/impl/model/interface/ppo_interface.py``
(1341 LoC). The structure mirrors the reference's train_step
(``ppo_interface.py:527``): reward shaping with KL penalty → GAE →
(group-)advantage normalization over the *whole* batch → minibatch loop with
one optimizer step each, using the decoupled/dual-clip actor loss.

Key layout difference: every per-token quantity is token-aligned on the
packed axis (logprob at position t = log p(token t+1 | ≤ t)), so the action
mask is "has a next token AND the next token is generated". GAE runs as one
associative scan over the flat packed batch (``areal_tpu.ops.ppo``), not a
CUDA kernel.
"""

import dataclasses
from typing import Dict, Optional

import jax
import jax.numpy as jnp
import numpy as np

from areal_tpu.api.data import MicroBatchSpec, SequenceSample
from areal_tpu.api.model import ModelInterface, PPOHyperparameters
from areal_tpu.base import program_store, tracing
from areal_tpu.ops import ppo as ppo_ops
from areal_tpu.parallel import multihost
from areal_tpu.train import batching
from areal_tpu.train import engine as engine_mod
from areal_tpu.train.engine import vmapped_forward, vmapped_next_token_logprobs


def _action_mask(arrays) -> jnp.ndarray:
    """[D, T] bool: positions whose *label* (next token) is a generated
    token of the same segment."""
    seg = arrays["segment_ids"]
    has_next = (seg > 0) & ~jax.vmap(ppo_ops.is_segment_end)(seg)
    pm = arrays["prompt_mask"].astype(bool)
    label_is_prompt = jnp.concatenate([pm[:, 1:], jnp.zeros_like(pm[:, :1])], 1)
    return has_next & ~label_is_prompt


def logprob_output_fn(params, cfg, arrays):
    """Token-aligned logprobs of the next token — the "inference" MFC that
    recomputes proximal logprobs (≈ ``ppo_interface.py:474``). Honors
    ``cfg.loss_chunk_size`` (no [T, vocab] logits at long context)."""
    return vmapped_next_token_logprobs(params, cfg, arrays)


def logprob_router_output_fn(params, cfg, arrays):
    """``logprob_output_fn`` for an MoE model whose caller passed the
    generation engine's routing (``routed_experts`` ``[D, T, L, top_k]``,
    -1 where the engine recorded nothing: ``GenOutput.output_routing``):
    ``[D, T, 3]`` float32 = the logprob, the layers of that token whose
    chosen expert SET in this recompute equals the engine's, and the
    layers compared. Top-k of a softmax is discontinuous, so a rounding
    difference between the two programs can pick another expert: this is
    the count of how often it did."""
    lp, routing = vmapped_next_token_logprobs(
        params, cfg, arrays, with_routing=True
    )
    mine = jnp.sort(routing.transpose(0, 2, 1, 3), axis=-1)  # [D, T, L, K]
    theirs = arrays["routed_experts"]
    known = (theirs >= 0).all(axis=-1) & (arrays["segment_ids"] > 0)[..., None]
    agree = known & (mine == jnp.sort(theirs, axis=-1)).all(axis=-1)
    return jnp.stack(
        [lp, agree.sum(-1).astype(lp.dtype), known.sum(-1).astype(lp.dtype)],
        axis=-1,
    )


def value_output_fn(params, cfg, arrays):
    """Per-token critic values [D, T] (zero on padding)."""
    values = vmapped_forward(params, cfg, arrays)[..., 0]
    return jnp.where(arrays["segment_ids"] > 0, values, 0.0)




@dataclasses.dataclass
class PPOActorInterface(ModelInterface):
    hp: PPOHyperparameters = dataclasses.field(default_factory=PPOHyperparameters)
    hf_family: Optional[str] = None

    def __post_init__(self):
        if self.hp.use_adaptive_kl:
            self.kl_ctl = ppo_ops.AdaptiveKLController(
                self.hp.kl_ctl, self.hp.adaptive_kl_target, self.hp.adaptive_kl_horizon
            )
        else:
            self.kl_ctl = ppo_ops.FixedKLController(self.hp.kl_ctl)
        self._last_ref_kl = 0.0
        # Built once so the engine's jit cache hits across train_step calls.
        self._actor_loss_fn = self._build_actor_loss()
        # Likewise the advantage pre-pass: its cache lives with the interface.
        self._prepass = program_store.stored_jit(
            self._build_prepass(), name="ppo/prepass", built_from=self.hp)
        # what it reads of a packed batch; no other array is sent to the device
        self._prepass_keys = {
            "segment_ids", "prompt_mask", "packed_logprobs", "rewards",
            "packed_ref_logprobs", "seq_no_eos_mask",
        }
        if not self.hp.disable_value:
            self._prepass_keys.add("values")
        if self.hp.group_adv_norm:
            self._prepass_keys.add("item_ids")

    def _build_actor_loss(self):
        hp = self.hp

        def actor_loss(params, cfg, arrays):
            mask = _action_mask(arrays)
            new_lp, aux = vmapped_next_token_logprobs(
                params, cfg, arrays, with_aux=True
            )
            old_lp = arrays["packed_logprobs"].astype(jnp.float32)
            prox = arrays.get("prox_logp")
            if hp.use_decoupled_loss and prox is not None:
                prox = prox.astype(jnp.float32)
            elif hp.recompute_logprob and prox is not None:
                # sync-PPO with recomputed logprobs: use them as "old"
                old_lp, prox = prox.astype(jnp.float32), None
            else:
                prox = None
            loss, stat = ppo_ops.actor_loss_fn(
                new_lp.reshape(-1),
                old_lp.reshape(-1),
                arrays["advantages"].astype(jnp.float32).reshape(-1),
                hp.eps_clip,
                mask.reshape(-1),
                c_clip=hp.c_clip,
                proximal_logprobs=None if prox is None else prox.reshape(-1),
                behav_imp_weight_cap=hp.behav_imp_weight_cap,
            )
            n = jnp.maximum(mask.sum(), 1)
            scalar_stats = {
                "actor_loss": loss,
                "importance_weight": jnp.sum(stat["importance_weight"]) / n,
                "actor_clip_ratio": jnp.sum(stat["clip_mask"]) / n,
                "approx_kl": jnp.sum(jnp.abs(stat["approx_kl"] * mask.reshape(-1))) / n,
            }
            return loss + aux, scalar_stats

        return actor_loss

    # -------------------------------------------------------------- #
    # proximal logprob recompute (actor_inf MFC)
    # -------------------------------------------------------------- #

    def inference(
        self, engine, sample: SequenceSample, mb_spec: MicroBatchSpec
    ) -> SequenceSample:
        routed = (
            "routed_experts" in sample.keys and engine.cfg.mlp_type == "moe"
        )
        with tracing.span("ppo/inference", n_mbs=mb_spec.n_mbs) as attrs:
            outs = engine.forward(
                sample, mb_spec,
                logprob_router_output_fn if routed else logprob_output_fn,
            )
            if routed:
                # (token, layer) pairs of this host's sequences
                attrs["router_agree"] = int(sum(o[:, 1].sum() for o in outs))
                attrs["router_total"] = int(sum(o[:, 2].sum() for o in outs))
                outs = [o[:, 0] for o in outs]
        main = sample.main_key()
        res = SequenceSample(
            keys={"prox_logp"},
            ids=list(sample.ids),
            seqlens={"prox_logp": [list(l) for l in sample.seqlens[main]]},
            data={"prox_logp": np.concatenate([o.astype(np.float32) for o in outs])},
        )
        return res

    # -------------------------------------------------------------- #
    # advantage computation over the full batch
    # -------------------------------------------------------------- #

    def _build_prepass(self):
        """The advantage pre-pass as ONE function of the packed ``[T]``
        arrays it reads and the KL coefficient: reward shaping, GAE and the
        normalisation. ``jax.jit`` specialises it on ``T``, on which optional
        keys (``values``, ``packed_ref_logprobs``, ``seq_no_eos_mask``) the
        batch carries and on the constants of ``self.hp``. The coefficient
        is a traced operand: under ``use_adaptive_kl`` it changes every
        step."""
        hp = self.hp

        def prepass(a, kl_coef):
            seg = a["segment_ids"]
            mask = _action_mask({k: v[None] for k, v in a.items()})[0]

            behav_lp = a["packed_logprobs"].astype(jnp.float32)
            ref_lp = a.get("packed_ref_logprobs", behav_lp)  # absent: zero KL penalty
            raw_v = a.get("values", jnp.zeros_like(behav_lp)).astype(jnp.float32)
            values = raw_v * mask

            reward_score = (
                a["rewards"].astype(jnp.float32) * hp.reward_output_scaling
                + hp.reward_output_bias
            )
            no_eos = a.get(
                "seq_no_eos_mask", jnp.zeros_like(reward_score, dtype=bool)
            ).astype(bool)

            # KL-penalized dense rewards + task reward at the *last action* token
            ref_kl = behav_lp - ref_lp.astype(jnp.float32)
            ref_kl_mean = jnp.sum(jnp.where(mask, ref_kl, 0.0)) / jnp.maximum(
                mask.sum(), 1
            )
            kl_rw = jnp.where(mask, -kl_coef * ref_kl, 0.0)
            nxt_mask = jnp.concatenate([mask[1:], jnp.zeros((1,), bool)])
            last_action = mask & ~nxt_mask
            score = jnp.clip(reward_score, -hp.max_reward_clip, hp.max_reward_clip)
            if hp.mask_no_eos_with_zero:
                score = jnp.where(no_eos, 0.0, score)
            rewards = kl_rw + jnp.where(last_action, score, 0.0)

            # next values: within the action span values[t+1]; at the last
            # action, bootstrap with the next token's value iff the sequence
            # was truncated (≈ cugae's seq_no_eos bootstrap).
            zero = jnp.zeros((1,), jnp.float32)
            next_values = jnp.where(
                nxt_mask,
                jnp.concatenate([values[1:], zero]),
                jnp.where(no_eos, jnp.concatenate([raw_v[1:], zero]), 0.0),
            )

            adv, ret = ppo_ops.segment_gae(
                rewards, values, next_values, seg, hp.discount, hp.gae_lambda,
                mask=mask, not_end=nxt_mask,
            )
            if hp.group_adv_norm:
                # every item holds a token, so ``T`` bounds the group count
                # and the program never specialises on the batch size; the
                # groups past the last item are empty (their count clamps
                # to 1) and nothing gathers them
                adv = ppo_ops.group_normalization(
                    adv, mask, a["item_ids"], num_groups=seg.shape[0]
                )
            elif hp.adv_norm:
                adv = ppo_ops.masked_normalization(adv, mask)
            return adv, ret, kl_rw, ref_kl_mean

        return prepass

    def _prepare(self, sample: SequenceSample) -> SequenceSample:
        """Compute advantages/returns on the whole batch (flat packed layout)
        and attach them as new keys — the analogue of the reference's
        pre-minibatch GAE + normalization block (``ppo_interface.py:527-647``).
        One ``ppo/prepare`` span per call, around the host's packing, one
        transfer in, ONE compiled program (``_build_prepass``) and one
        transfer out. ``tracing``'s compile listener stamps the span with
        ``compiled`` = 1 (and ``compile_s``) when the call built a program
        (a padded length or a set of keys not met before); a call that
        built none carries neither."""
        main = sample.main_key()
        with tracing.span(
            "ppo/prepare", n_seqs=sample.bs,
            n_tokens=sum(sum(l) for l in sample.seqlens[main]),
        ):
            pb = batching.pack_sequences(sample, n_rows=1, pad_multiple=128)
            out = self._prepass(*jax.device_put((
                {k: v[0] for k, v in pb.arrays.items()
                 if k in self._prepass_keys},
                np.float32(self.kl_ctl.value),
            )))
            return self._attach(sample, pb, *out)

    def _attach(self, sample, pb, adv, ret, kl_rw, ref_kl_mean):
        # ONE device->host transfer for everything the host needs
        adv, ret, kl_rw, ref_kl_mean = jax.device_get(
            (adv, ret, kl_rw, ref_kl_mean)
        )
        self._last_ref_kl = float(ref_kl_mean)
        main = sample.main_key()
        seqlens = {"advantages": [list(l) for l in sample.seqlens[main]],
                   "returns": [list(l) for l in sample.seqlens[main]],
                   "kl_rewards": [list(l) for l in sample.seqlens[main]]}
        data = {}
        for key, arr in (("advantages", adv), ("returns", ret), ("kl_rewards", kl_rw)):
            per_seq = pb.unpack(np.asarray(arr)[None])
            data[key] = np.concatenate(per_seq).astype(np.float32)
        sample.update_(
            SequenceSample(
                keys=set(seqlens), ids=list(sample.ids), seqlens=seqlens, data=data
            )
        )
        return sample

    # -------------------------------------------------------------- #
    # train step
    # -------------------------------------------------------------- #

    def train_step(
        self, engine, sample: SequenceSample, mb_spec: MicroBatchSpec
    ) -> Dict[str, float]:
        hp = self.hp
        with tracing.span("ppo/train_step") as span_attrs:
            sample = self._prepare(sample)
            # engine.train_batch is collective: the minibatch COUNT must agree
            # across hosts even when per-host batch sizes differ (a starved host
            # with a partial batch must not run fewer collective calls)
            n_mb = int(
                multihost.allreduce_min(np.int64(min(hp.ppo_n_minibatches, sample.bs)))
            )
            mbs = sample.split(max(n_mb, 1))
            span_attrs["n_mbs"] = len(mbs)   # PPO minibatches: optimizer steps
            # pipelined minibatch loop: pack+put of minibatch n+1 overlaps the
            # in-flight jitted step for minibatch n (serial loop when
            # AREAL_TRAIN_PREFETCH is off). No host collectives may run between
            # these dispatches — ours (the kl_ctl allreduce) sit after the loop.
            all_stats = engine.train_batches_pipelined(
                mbs, mb_spec, self._actor_loss_fn, fetch_stats=False
            )
            engine.version += 1
            # minibatch-mean WITHOUT a device pull (deferred-stats path: the
            # trainer fetches once per logging interval, not per step)
            out = engine_mod.mean_stats_dicts(all_stats)
            # Adaptive KL control tracks policy-vs-reference divergence (the
            # signed masked mean over action tokens), like the reference
            # (ppo_interface.py:973-978) — NOT the PPO update KL. The update is
            # fed the GLOBAL mean so per-host controllers never drift apart.
            tot = multihost.allreduce_sum(
                np.asarray([self._last_ref_kl * sample.bs, sample.bs], np.float64)
            )
            ref_kl_global = float(tot[0] / max(tot[1], 1))
            self.kl_ctl.update(ref_kl_global, int(tot[1]))
            out["kl_ctl"] = self.kl_ctl.value
            out["ref_kl"] = ref_kl_global
            out["n_seqs"] = sample.bs
            if not engine_mod.train_prefetch_enabled():
                # legacy per-step blocking behavior for callers that asked for it
                out = engine_mod.fetch_stats_dict(out)
            return out


@dataclasses.dataclass
class PPOCriticInterface(ModelInterface):
    hp: PPOHyperparameters = dataclasses.field(default_factory=PPOHyperparameters)
    hf_family: Optional[str] = None
    # Share the ACTOR's controller here: with use_adaptive_kl the coefficient
    # adapts every actor step, and the critic's value targets must be shaped
    # with the same coefficient or they diverge from the actor's advantages
    # (the reference shares one kl_adapter, ``ppo_interface.py``).
    kl_ctl: Optional[object] = None

    def __post_init__(self):
        if self.kl_ctl is None:
            # standalone construction: mirror the actor's controller choice,
            # or adaptive-KL critics silently fall back to a fixed coefficient
            if self.hp.use_adaptive_kl:
                self.kl_ctl = ppo_ops.AdaptiveKLController(
                    self.hp.kl_ctl,
                    self.hp.adaptive_kl_target,
                    self.hp.adaptive_kl_horizon,
                )
            else:
                self.kl_ctl = ppo_ops.FixedKLController(self.hp.kl_ctl)
        self._actor_helper = PPOActorInterface(hp=self.hp)
        # the helper only runs _prepare (reward shaping + GAE); its KL
        # coefficient must track the shared controller, and its update()
        # must never fire (the actor owns updates)
        self._actor_helper.kl_ctl = self.kl_ctl
        hp = self.hp

        def critic_loss(params, cfg, arrays):
            mask = _action_mask(arrays)
            values, aux = vmapped_forward(params, cfg, arrays, with_aux=True)
            new_values = jnp.where(
                arrays["segment_ids"] > 0, values[..., 0], 0.0
            )
            loss, stat = ppo_ops.critic_loss_fn(
                new_values.reshape(-1),
                arrays["values"].astype(jnp.float32).reshape(-1),
                arrays["returns"].astype(jnp.float32).reshape(-1),
                hp.value_eps_clip,
                mask.reshape(-1),
            )
            n = jnp.maximum(mask.sum(), 1)
            return loss + aux, {
                "critic_loss": loss,
                "value_clip_ratio": jnp.sum(stat["clip_mask"]) / n,
            }

        self._critic_loss_fn = critic_loss

    def inference(
        self, engine, sample: SequenceSample, mb_spec: MicroBatchSpec
    ) -> SequenceSample:
        with tracing.span("ppo/inference", n_mbs=mb_spec.n_mbs):
            outs = engine.forward(sample, mb_spec, value_output_fn)
        main = sample.main_key()
        return SequenceSample(
            keys={"values"},
            ids=list(sample.ids),
            seqlens={"values": [list(l) for l in sample.seqlens[main]]},
            data={"values": np.concatenate([o.astype(np.float32) for o in outs])},
        )

    def train_step(
        self, engine, sample: SequenceSample, mb_spec: MicroBatchSpec
    ) -> Dict[str, float]:
        hp = self.hp
        with tracing.span("ppo/train_step") as span_attrs:
            sample = self._actor_helper._prepare(sample)
            n_mb = int(
                multihost.allreduce_min(np.int64(min(hp.ppo_n_minibatches, sample.bs)))
            )
            mbs = sample.split(max(n_mb, 1))
            span_attrs["n_mbs"] = len(mbs)   # PPO minibatches: optimizer steps
            all_stats = engine.train_batches_pipelined(
                mbs, mb_spec, self._critic_loss_fn, fetch_stats=False
            )
            engine.version += 1
            out = engine_mod.mean_stats_dicts(all_stats)
            if not engine_mod.train_prefetch_enabled():
                out = engine_mod.fetch_stats_dict(out)
            return out
