"""Packing + TrainEngine tests on the 8-device virtual CPU mesh.

Counterpart of the reference's CPU ``mock_train`` backend tests: real pjit
sharding (d2×f2×m2 = 8 devices), tiny model, real optimizer steps.
"""

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from areal_tpu.api.data import MicroBatchSpec, SequenceSample
from areal_tpu.models.config import ModelConfig
from areal_tpu.ops import ppo as ppo_ops
from areal_tpu.parallel.mesh import ParallelConfig
from areal_tpu.train import batching
from areal_tpu.train.engine import OptimizerConfig, TrainEngine, vmapped_forward

TINY = ModelConfig(
    n_layers=2, n_q_heads=4, n_kv_heads=2, head_dim=8, hidden_dim=32,
    intermediate_dim=64, vocab_size=128, dtype="float32",
)


def _make_sample(rng, n_items=6, with_reward=False):
    seqlens = [int(n) for n in rng.integers(4, 12, size=n_items)]
    data = {
        "packed_input_ids": np.concatenate(
            [rng.integers(0, 128, size=n).astype(np.int64) for n in seqlens]
        ),
        "prompt_mask": np.concatenate(
            [
                np.r_[np.ones(2, np.bool_), np.zeros(n - 2, np.bool_)]
                for n in seqlens
            ]
        ),
    }
    if with_reward:
        data["rewards"] = rng.normal(size=n_items).astype(np.float32)
    return SequenceSample.from_default(
        ids=list(range(n_items)), seqlens=seqlens, data=data
    )


def test_pack_roundtrip(rng):
    sample = _make_sample(rng, with_reward=True)
    pb = batching.pack_sequences(sample, n_rows=4, pad_multiple=16)
    assert pb.arrays["input_ids"].shape == pb.arrays["segment_ids"].shape
    # every sequence present exactly once, token-aligned
    outs = pb.unpack(pb.arrays["input_ids"])
    full = sample.data["packed_input_ids"]
    offsets = np.cumsum([0] + [l[0] for l in sample.seqlens["packed_input_ids"]])
    for p, got in zip(pb.placements, outs):
        np.testing.assert_array_equal(
            got, full[offsets[p.item_idx] : offsets[p.item_idx] + p.length]
        )
    # scalar broadcast: rewards constant over each segment
    for p in pb.placements:
        seg = pb.arrays["rewards"][p.row, p.start : p.start + p.length]
        assert np.all(seg == sample.data["rewards"][p.item_idx])
    # padding rows zero
    assert np.all(
        pb.arrays["input_ids"][pb.arrays["segment_ids"] == 0] == 0
    )


def test_pack_balance(rng):
    lens = [100, 1, 1, 1, 50, 50, 1, 1]
    rows = batching.plan_rows(lens, 2)
    loads = [sum(l for l, r in zip(lens, rows) if r == j) for j in range(2)]
    assert abs(loads[0] - loads[1]) <= 100 - 50  # LPT puts 100 alone-ish
    assert max(loads) <= 104


def _sft_loss(params, cfg, arrays):
    logits = vmapped_forward(params, cfg, arrays)
    lp = jax.vmap(ppo_ops.gather_packed_shifted_log_probs)(
        logits, arrays["input_ids"], arrays["segment_ids"]
    )
    seg = arrays["segment_ids"]
    has_next = (seg > 0) & ~jax.vmap(ppo_ops.is_segment_end)(seg)
    mask = has_next & ~arrays["prompt_mask"]
    n = jnp.maximum(mask.sum(), 1)
    loss = -jnp.sum(jnp.where(mask, lp, 0.0)) / n
    return loss, {"n_tokens": n}


@pytest.fixture(scope="module")
def engine():
    eng = TrainEngine(
        TINY,
        parallel=ParallelConfig(data=2, fsdp=2, model=2),
        optimizer=OptimizerConfig(lr=1e-3, lr_scheduler_type="cosine"),
    )
    eng.init_random(0)
    eng.setup_optimizer(total_train_steps=50)
    return eng


def test_sharded_init(engine):
    # wq is [L, E, H*D]: embed axis sharded over fsdp, heads over model
    spec = engine.params["layers"]["attn"]["wq"].sharding.spec
    assert spec == jax.sharding.PartitionSpec(None, "fsdp", "model")


def test_train_batch_loss_decreases(engine, rng):
    sample = _make_sample(rng, n_items=8)
    spec = MicroBatchSpec(n_mbs=2, max_tokens_per_mb=64)
    losses = []
    for _ in range(8):
        stats = engine.train_batch(sample, spec, _sft_loss)
        losses.append(stats["loss"])
    assert losses[-1] < losses[0]
    assert stats["grad_norm"] > 0
    assert stats["lr"] > 0


@pytest.mark.parametrize(
    "par", [ParallelConfig(), ParallelConfig(data=2, fsdp=2, model=2)],
    ids=["single", "d2f2m2"],
)
def test_no_recompile_across_rounds(rng, par):
    """Identical-shape train rounds must backend-compile exactly once
    (VERDICT r3 weak #1). Two past offenders: (a) jit(tx.init) left the
    optax count scalars SingleDeviceSharding while the train step emitted
    NamedSharding(mesh, P()) — the sharding-in-types aval mismatch forced
    a FULL second train-step compile on round 2 of every run; (b) on
    multi-device meshes GSPMD's inferred output shardings for the opt
    state drifted from the init-time ones — a trace-cache HIT but a
    second backend compile (now pinned via out_shardings)."""
    from jax._src import monitoring

    eng = TrainEngine(
        TINY, parallel=par,
        optimizer=OptimizerConfig(lr=1e-3),
    )
    eng.init_random(0)
    eng.setup_optimizer(total_train_steps=50)
    sample = _make_sample(rng, n_items=8)
    spec = MicroBatchSpec(n_mbs=1, max_tokens_per_mb=256)
    compiles = []

    def on_dur(key, dur, **kw):
        if key == "/jax/core/compile/backend_compile_duration":
            compiles.append(dur)

    monitoring.register_event_duration_secs_listener(on_dur)
    try:
        eng.train_batch(sample, spec, _sft_loss, fetch_stats=False)
        n_round1 = len(compiles)
        assert n_round1 >= 1  # round 1 really compiled the step
        for _ in range(3):
            eng.train_batch(sample, spec, _sft_loss, fetch_stats=False)
        assert len(compiles) == n_round1, (
            f"rounds 2-4 backend-compiled {len(compiles) - n_round1} more "
            "program(s) at identical shapes"
        )
    finally:
        # never leak the listener into subsequent tests
        monitoring.unregister_event_duration_listener(on_dur)


def test_start_phases_are_spans_and_steps_say_what_they_built(rng):
    """``train_engine/start`` around the constructor, then a span a phase
    of the state's making as the caller asks for them; the first train
    step builds its program under ``train_pipe/dispatch`` (``compiled``, a
    ``compile/program`` child), the second builds nothing."""
    from areal_tpu.base import tracing

    tracing.drain()
    eng = TrainEngine(TINY, optimizer=OptimizerConfig(lr=1e-3))
    eng.init_random(0)
    eng.setup_optimizer(total_train_steps=50)
    spans = tracing.drain()
    phases = [s for s in spans if s["name"].startswith("train_engine/start")]
    assert [s["name"] for s in phases] == [
        "train_engine/start", "train_engine/start/params",
        "train_engine/start/optimizer"]
    for name, program in (("params", "jit(init_params)"),
                          ("optimizer", "jit(init_fn)")):    # optax's name
        (phase,) = [s for s in phases if s["name"].endswith(name)]
        built = [s["attrs"]["fun_name"] for s in spans
                 if s["name"] == "compile/program"
                 and s["parent_id"] == phase["span_id"]]
        assert program in built and phase["attrs"]["compiled"] == len(built)
        assert 0 < phase["attrs"]["compile_s"] <= phase["dur_s"]

    sample = _make_sample(rng, n_items=8)
    spec = MicroBatchSpec(n_mbs=1, max_tokens_per_mb=256)
    rounds = []
    for _ in range(2):
        eng.train_batch(sample, spec, _sft_loss, fetch_stats=False)
        rounds.append(tracing.drain())
    (dispatch,) = [s for s in rounds[0] if s["name"] == "train_pipe/dispatch"]
    assert dispatch["attrs"]["compiled"] >= 1
    assert "jit(train_step)" in [
        s["attrs"]["fun_name"] for s in rounds[0]
        if s["name"] == "compile/program"
        and s["parent_id"] == dispatch["span_id"]]
    assert not [s for s in rounds[1] if s["name"] == "compile/program"]
    assert all("compiled" not in s.get("attrs", {}) for s in rounds[1])


def test_forward_unpacks_per_sequence(engine, rng):
    sample = _make_sample(rng, n_items=5)

    def logprob_fn(params, cfg, arrays):
        logits = vmapped_forward(params, cfg, arrays)
        return jax.vmap(ppo_ops.gather_packed_shifted_log_probs)(
            logits, arrays["input_ids"], arrays["segment_ids"]
        )

    outs = engine.forward(sample, MicroBatchSpec(n_mbs=2), logprob_fn)
    lens = [l[0] for l in sample.seqlens["packed_input_ids"]]
    assert len(outs) == 5
    # outputs come back in the sample's original item order despite the
    # reordering micro-batch split
    assert [o.shape[0] for o in outs] == lens


def test_checkpoint_roundtrip(engine, rng, tmp_path):
    sample = _make_sample(rng, n_items=4)
    path = str(tmp_path / "ckpt")
    engine.save_checkpoint(path)
    before = engine.eval_batch(sample, MicroBatchSpec(), _sft_loss)["loss"]
    engine.train_batch(sample, MicroBatchSpec(), _sft_loss)
    engine.load_checkpoint(path)
    after = engine.eval_batch(sample, MicroBatchSpec(), _sft_loss)["loss"]
    assert before == pytest.approx(after, rel=1e-6)


def test_micro_batch_split_respects_row_capacity():
    """ADVICE round 1 (medium): the token budget only bounded the average;
    a [16000, 500, 16000] batch with budget 16384 crashed the packer."""
    from areal_tpu.api.data import MicroBatchSpec, SequenceSample
    from areal_tpu.train import batching

    lens = [16000, 500, 16000]
    sample = SequenceSample.from_default(
        ids=[0, 1, 2],
        seqlens=lens,
        data={"packed_input_ids": np.zeros(sum(lens), np.int64)},
    )
    parts = batching.split_into_micro_batches(
        sample, n_mbs=1, max_tokens_per_mb=16384, n_rows=1
    )
    for part in parts:
        pb = batching.pack_sequences(part, n_rows=1, capacity=16384)
        assert pb.capacity == 16384

    # a single over-long sequence is rejected at intake with a clear error
    big = SequenceSample.from_default(
        ids=[0],
        seqlens=[20000],
        data={"packed_input_ids": np.zeros(20000, np.int64)},
    )
    with pytest.raises(ValueError, match="can never be packed"):
        batching.split_into_micro_batches(
            big, n_mbs=1, max_tokens_per_mb=16384, n_rows=1
        )


def test_remat_policy_and_unroll_grad_parity(rng):
    """remat_policy / layer_scan_unroll are pure execution knobs: losses and
    gradients are identical across every combination."""
    import dataclasses

    from areal_tpu.models import transformer as tfm

    base = dataclasses.replace(TINY)
    T = 32
    ids = jnp.asarray(rng.integers(0, 128, T).astype(np.int32))
    seg = jnp.asarray(np.r_[np.ones(20, np.int32) * 1, np.ones(12, np.int32) * 2])
    pos = jnp.asarray(np.r_[np.arange(20), np.arange(12)].astype(np.int32))
    params = tfm.init_params(base, jax.random.key(0))

    def loss(cfg):
        def f(p):
            out = tfm.forward_packed(p, cfg, ids, seg, pos)
            return jnp.sum(out.astype(jnp.float32) ** 2) * 1e-4
        return jax.value_and_grad(f)(params)

    ref_l, ref_g = loss(base)
    for policy in ("full", "dots", "dots_attn", "none"):
        for unroll in (1, 2):
            cfg = dataclasses.replace(
                base, remat_policy=policy, layer_scan_unroll=unroll
            )
            l, g = loss(cfg)
            assert jnp.allclose(l, ref_l, atol=1e-6), (policy, unroll)
            for a, b in zip(jax.tree.leaves(g), jax.tree.leaves(ref_g)):
                np.testing.assert_allclose(
                    np.asarray(a), np.asarray(b), atol=1e-5,
                    err_msg=f"{policy}/{unroll}",
                )


def test_chunked_loss_matches_dense(rng):
    """cfg.loss_chunk_size (blockwise LM-head cross-entropy, the 32k-logit
    memory saver) must match the dense loss in value AND gradients — incl.
    a chunk size that does not divide T (rounded down to a divisor)."""
    import dataclasses

    from areal_tpu.interfaces.sft import sft_loss_fn
    from areal_tpu.models import transformer as tfm

    cfg = TINY
    params = tfm.init_params(cfg, jax.random.key(3))
    T = 64
    arrays = {
        "input_ids": jnp.asarray(rng.integers(1, 128, (2, T)), jnp.int32),
        "segment_ids": jnp.asarray(
            np.tile(np.r_[np.ones(50), np.zeros(T - 50)], (2, 1)), jnp.int32
        ),
        "positions": jnp.asarray(np.tile(np.arange(T), (2, 1)), jnp.int32),
        "prompt_mask": jnp.asarray(
            np.tile(np.r_[np.ones(5), np.zeros(T - 5)], (2, 1)), bool
        ),
    }
    l_dense, _ = sft_loss_fn(params, cfg, arrays)
    g_dense = jax.grad(lambda p: sft_loss_fn(p, cfg, arrays)[0])(params)
    for chunk in (16, 24):  # 24 does not divide 64 -> rounds down to 16
        cfgc = dataclasses.replace(cfg, loss_chunk_size=chunk)
        l_c, _ = sft_loss_fn(params, cfgc, arrays)
        np.testing.assert_allclose(float(l_dense), float(l_c), atol=1e-5)
        g_c = jax.grad(lambda p: sft_loss_fn(p, cfgc, arrays)[0])(params)
        for a, b in zip(jax.tree.leaves(g_dense), jax.tree.leaves(g_c)):
            np.testing.assert_allclose(
                np.asarray(a), np.asarray(b), atol=1e-5
            )


def _nan_on_empty_loss(params, cfg, arrays):
    """SFT-style loss WITHOUT the max(n, 1) clamp: an empty action mask
    yields 0/0 = nan — the loss-fn shape the engine must tolerate on
    all-padding micro-batches (engine comment in eval_batch: nan means the
    mb's weight is 0)."""
    logits = vmapped_forward(params, cfg, arrays)
    lp = jax.vmap(ppo_ops.gather_packed_shifted_log_probs)(
        logits, arrays["input_ids"], arrays["segment_ids"]
    )
    seg = arrays["segment_ids"]
    has_next = (seg > 0) & ~jax.vmap(ppo_ops.is_segment_end)(seg)
    mask = has_next & ~arrays["prompt_mask"]
    loss = -jnp.sum(jnp.where(mask, lp, 0.0)) / mask.sum()
    return loss, {"n_tokens": mask.sum()}


def _fresh_tiny_engine():
    eng = TrainEngine(
        TINY, parallel=ParallelConfig(), optimizer=OptimizerConfig(lr=1e-3)
    )
    eng.init_random(0)
    eng.setup_optimizer(total_train_steps=50)
    return eng


class TestTrainGuard:
    """On-device finite-ness guard (trainer survivability, PR 3)."""

    def test_injected_nan_step_skips_update_params_byte_identical(self, rng):
        from areal_tpu.base import faults

        eng = _fresh_tiny_engine()
        sample = _make_sample(rng, n_items=6)
        spec = MicroBatchSpec(n_mbs=2, max_tokens_per_mb=64)
        eng.train_batch(sample, spec, _sft_loss)  # warm; params move
        before = [np.asarray(l).copy() for l in jax.tree.leaves(eng.params)]
        opt_before = [
            np.asarray(l).copy() for l in jax.tree.leaves(eng.opt_state)
        ]
        try:
            faults.inject("train.step", action="trip", times=1)
            stats = eng.train_batch(sample, spec, _sft_loss)
        finally:
            faults.reset()
        # the poisoned update was selected away: params AND opt state
        # (Adam moments + count) byte-identical to the pre-step values
        assert stats["guard/step_ok"] == 0.0
        for a, b in zip(before, jax.tree.leaves(eng.params)):
            np.testing.assert_array_equal(a, np.asarray(b))
        for a, b in zip(opt_before, jax.tree.leaves(eng.opt_state)):
            np.testing.assert_array_equal(a, np.asarray(b))
        # next (clean) step trains normally
        stats = eng.train_batch(sample, spec, _sft_loss)
        assert stats["guard/step_ok"] == 1.0
        assert any(
            not np.array_equal(a, np.asarray(b))
            for a, b in zip(before, jax.tree.leaves(eng.params))
        )

    def test_empty_microbatch_nan_does_not_misfire_guard(self, rng):
        """A zero-weight (all-padding / all-prompt) micro-batch whose loss
        is 0/0 = nan must be SELECTED out, not scaled out — the guard must
        see a finite step and the other micro-batch must still train."""
        eng = _fresh_tiny_engine()
        lens = [10, 10, 10]
        data = {
            "packed_input_ids": rng.integers(
                0, 128, sum(lens)
            ).astype(np.int64),
            # one item is ALL prompt: zero action tokens -> its micro-batch
            # (forced by the tiny token budget) carries loss weight 0 and a
            # nan loss under _nan_on_empty_loss
            "prompt_mask": np.concatenate([
                np.r_[np.ones(2, np.bool_), np.zeros(8, np.bool_)],
                np.ones(10, np.bool_),
                np.r_[np.ones(2, np.bool_), np.zeros(8, np.bool_)],
            ]),
        }
        sample = SequenceSample.from_default(
            ids=[0, 1, 2], seqlens=lens, data=data
        )
        # one warm step so the lr warmup is past 0 (step-0 updates are
        # all-zero by schedule, which would mask the thing under test)
        eng.train_batch(
            sample, MicroBatchSpec(n_mbs=3, max_tokens_per_mb=16),
            _nan_on_empty_loss,
        )
        before = [np.asarray(l).copy() for l in jax.tree.leaves(eng.params)]
        stats = eng.train_batch(
            sample, MicroBatchSpec(n_mbs=3, max_tokens_per_mb=16),
            _nan_on_empty_loss,
        )
        assert stats["guard/step_ok"] == 1.0, "guard misfired on empty mb"
        assert np.isfinite(stats["loss"]) and np.isfinite(stats["grad_norm"])
        assert any(
            not np.array_equal(a, np.asarray(b))
            for a, b in zip(before, jax.tree.leaves(eng.params))
        )

    def test_eval_all_padding_mb_nan_has_zero_weight(self, rng):
        """Pins the engine comment in eval_batch: an all-padding packed
        buffer can evaluate to a nan loss, and the host-side weighting must
        zero it out rather than poison the epoch mean."""
        eng = _fresh_tiny_engine()
        sample = _make_sample(rng, n_items=4)
        _, packed, _ = eng._make_micro_batches(sample, MicroBatchSpec())
        empty = batching.empty_like(packed[0])
        ev = eng._get_jitted("eval", _nan_on_empty_loss)
        loss = np.asarray(
            jax.device_get(ev(eng.params, eng._put_batch(empty))[0])
        )
        assert np.isnan(loss)  # the raw all-padding loss IS nan...
        out = eng.eval_batch(sample, MicroBatchSpec(), _nan_on_empty_loss)
        assert np.isfinite(out["loss"])  # ...but the weighted mean is not


class TestAsyncSaveHF:
    def test_async_write_lands_and_runs_post_write(self, engine, tmp_path):
        import os

        path = str(tmp_path / "ckpt_async")
        flag = []
        t = engine.save_hf(
            path, "llama", async_write=True,
            post_write=lambda: flag.append(1),
        )
        assert t is not None
        t.join()
        assert t._areal_exc is None
        assert flag == [1]
        assert os.path.exists(os.path.join(path, "model.safetensors"))
        # the export is COMMITTED (a manifest) and round-trips through disk
        from areal_tpu.base import recover

        assert recover.read_manifest(path) is not None
        back = TrainEngine(TINY, ParallelConfig(), OptimizerConfig())
        back.load_hf(path)
        for a, b in zip(jax.tree.leaves(engine.params),
                        jax.tree.leaves(back.params)):
            np.testing.assert_allclose(
                np.asarray(a, np.float32), np.asarray(b, np.float32),
                atol=1e-6)

    def test_async_write_failure_is_stored_not_swallowed(
        self, engine, monkeypatch
    ):
        """Review finding r5: a failed background write must surface to
        the joiner (trainer's _join_publish raises), not die silently."""
        from areal_tpu.models import hf as hf_conv

        def boom(*a, **k):
            raise OSError("disk full")

        monkeypatch.setattr(hf_conv, "save_hf_checkpoint", boom)
        t = engine.save_hf("/tmp/nowhere_ckpt", "qwen2", async_write=True)
        t.join()
        assert isinstance(t._areal_exc, OSError)
