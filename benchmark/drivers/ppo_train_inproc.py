"""PPO actor iterations against an in-process ``TrainEngine``.

One iteration is what the trainer worker does with a rollout batch under
the decoupled loss: ``actor.inference`` (proximal log-probs under the
current policy) and ``actor.train_step`` (advantages, packing by the
repo's packer, one optimizer step), on the next of a seeded set of
pre-drawn batches. The host's packing is inside the window: it is a layer.

Set-up: weights from the seed (one jitted call), the optimizer state, one
iteration on EVERY batch of the set (the advantage pre-pass has shapes of
its own for each batch), until the engine's jit cache stops growing. The
window: iterations until ``--seconds`` have passed, then one
``device_get`` of every iteration's statistics, which drains the device.
After it: the inference pass's log-probs of a seeded sample against the
plain reference, on the parameters as they are then.
"""

from typing import Dict, List

import jax
import jax.numpy as jnp
import numpy as np

from benchmark import correct, flops, sut, traffic_gen, weights


def _sample(batch: traffic_gen.TrainBatch):
    from areal_tpu.api.data import SequenceSample

    n = len(batch.seqlens)
    return SequenceSample.from_default(
        ids=list(range(n)), seqlens=batch.seqlens,
        data={
            "packed_input_ids": batch.input_ids,
            "prompt_mask": batch.prompt_mask,
            "packed_logprobs": batch.behav_logprobs,
            "packed_ref_logprobs": batch.behav_logprobs,
            "rewards": batch.rewards,
            "seq_no_eos_mask": np.ones(n, bool),
        },
    )


def run(bench) -> Dict:
    from areal_tpu.api.data import MicroBatchSpec
    from areal_tpu.api.model import PPOHyperparameters, make_interface
    from areal_tpu.models import transformer as tfm
    from areal_tpu.parallel.mesh import ParallelConfig, param_shardings
    from areal_tpu.train.engine import OptimizerConfig, TrainEngine

    arch, mix = bench.arch, bench.mix
    cfg = sut.model_config(arch, mix.get("model_overrides", {}))
    tr = mix["trainer"]
    eng = TrainEngine(
        cfg, ParallelConfig(), OptimizerConfig(lr=tr["lr"]),
        param_dtype=tr["param_dtype"],
    )
    eng.params = weights.make_weights(
        sut.weight_shapes(cfg, tr["param_dtype"]), bench.seed,
        jnp.dtype(tr["param_dtype"]),
        out_shardings=param_shardings(eng.mesh, tfm.param_logical_axes(cfg)),
    )
    bench.mark("weights")
    eng.setup_optimizer(tr["total_train_steps"])
    bench.mark("optimizer")
    actor = make_interface("ppo_actor", hp=PPOHyperparameters(**mix["ppo"]))
    spec = MicroBatchSpec(n_mbs=1, max_tokens_per_mb=mix["max_tokens_per_batch"])

    batches = traffic_gen.train_batches(mix, bench.seed, cfg.vocab_size)
    bench.mark("batches")
    pending_stats: List[Dict] = []

    def iteration(batch: traffic_gen.TrainBatch):
        with bench.span("make_sample"):
            sample = _sample(batch)
        with bench.span("ppo.inference"):
            sample.update_(actor.inference(eng, sample, spec))
        with bench.span("ppo.train_step"):
            pending_stats.append(actor.train_step(eng, sample, spec))
        bench.poll()

    # ---- warm-up: every batch of the set, until nothing new compiles --- #
    for _ in range(3):
        for b in batches:
            iteration(b)
        bench.mark("warm_pass")
        seen = eng.n_jit_entries()
        for b in batches[:2]:
            iteration(b)
        if eng.n_jit_entries() == seen:
            break
    jax.device_get(pending_stats)
    pending_stats.clear()

    # ---- the window ----------------------------------------------------- #
    jit0 = eng.n_jit_entries()
    done: List[traffic_gen.TrainBatch] = []
    bench.window_open()
    i = 0
    while bench.window_due():
        with bench.span("iteration"):
            iteration(batches[i % len(batches)])
        done.append(batches[i % len(batches)])
        i += 1
    stats = jax.device_get(pending_stats)   # the one drain
    bench.window_close()
    jit1 = eng.n_jit_entries()

    # ---- counts ---------------------------------------------------------- #
    def bad(s: Dict) -> bool:
        loss = float(np.asarray(s.get("actor_loss", s.get("loss", np.nan))))
        gn = float(np.asarray(s.get("grad_norm", np.nan)))
        return not (np.isfinite(loss) and np.isfinite(gn) and gn > 0.0)

    failed = sum(bad(s) for s in stats)
    seqlens = [l for b in done for l in b.seqlens]
    tokens = sum(seqlens)
    cap = mix["max_tokens_per_batch"]
    bench.counters["model_flops"] = flops.train_flops(
        arch, seqlens) + flops.forward_flops(arch, seqlens)
    bench.facts["iteration_seqlens"] = [b.seqlens for b in done]

    # ---- correctness, outside the window ---------------------------------- #
    chk = mix["check"]
    rng = np.random.default_rng([bench.seed, 9])
    lens = traffic_gen.draw_lengths(chk["seq_len"], rng, chk["n_sequences"])
    cb = traffic_gen.TrainBatch(
        seqlens=lens.tolist(),
        input_ids=rng.integers(1, cfg.vocab_size, int(lens.sum())).astype(np.int64),
        prompt_mask=np.zeros(int(lens.sum()), bool),
        behav_logprobs=np.zeros(int(lens.sum()), np.float32),
        rewards=np.zeros(len(lens), np.float32),
    )
    sample = _sample(cb)
    got = actor.inference(eng, sample, spec).data["prox_logp"]
    offs = np.r_[0, np.cumsum(lens)]
    samples = []
    for j, n in enumerate(lens):
        seq_lp = got[offs[j]: offs[j + 1]]
        samples.append({
            "tokens": cb.input_ids[offs[j]: offs[j + 1]].tolist(),
            "start": 1,
            # label-aligned: position t holds log p(token t+1); the last
            # position of a sequence has no label
            "logprobs": seq_lp[: n - 1].tolist(),
        })
    check = correct.check_logprobs(eng.params, arch, tr["param_dtype"], samples)
    check["jit_entries_added_in_window"] = jit1 - jit0
    if jit1 != jit0:
        check["correct"] = False
        check["reason"] = "the trainer specialised a program inside the window"
    if failed:
        check["correct"] = False
        check["reason"] = f"{failed} iterations with non-finite loss or no gradient"

    return {
        "attempted": len(done), "failed": failed,
        "end_to_end": {"train_tokens_per_s": tokens / bench.window_s},
        "check": check,
        "info": {
            "iterations": len(done), "tokens_in_window": tokens,
            "packing_fill": tokens / (cap * max(len(done), 1)),
            "batches_in_set": len(batches),
            "iteration_s_median": float(np.median(bench.spans("iteration")))
            if done else None,
            "last_stats": {k: float(np.asarray(v)) for k, v in stats[-1].items()
                           if np.ndim(v) == 0} if stats else {},
        },
    }
