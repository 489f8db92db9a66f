"""What ``GenerationEngine`` promises a client, whatever the model: ten
checks, each a function of ``(make_engine, cfg, params)``.

Not collected itself. A model family's test file runs the whole contract
over its own engine with one parametrised test::

    @pytest.mark.parametrize("check", engine_contract.CHECKS)
    def test_engine_contract(params, check):
        engine_contract.run(
            check, functools.partial(_engine, params), CFG, params)

``make_engine(**kw)`` builds the family's engine at its test sizes (at
least two slots, an output cap of at least 24) and passes ``stop_token_ids``
and ``pipeline_chunks`` through. The checks hold the engine to ITSELF (a
greedy run is its own reference), so they need no reference model: that
the tokens are the model's is each family's own test.
"""

from unittest import mock

import jax
import numpy as np

from areal_tpu.gen.engine import GenRequest

STEPS = 4       # one chunk length everywhere: one chunk program an engine
N_NEW = 12

_greedy_refs = {}


def _prompt(cfg, n=5, seed=0):
    rng = np.random.default_rng(seed)
    return [int(x) for x in rng.integers(1, cfg.vocab_size, size=n)]


def _greedy(eng, prompt, n_new=N_NEW, rid="ref", **kw):
    eng.submit(GenRequest(
        rid=rid, input_ids=list(prompt), max_new_tokens=n_new, greedy=True,
        **kw))
    (out,) = eng.run_until_done(decode_steps=STEPS)
    return out


def _reference(make_engine, cfg):
    """The prompt every stop check uses and its greedy continuation, from
    a fresh engine with no stop token: built once a family."""
    if make_engine.func not in _greedy_refs:
        prompt = _prompt(cfg)
        _greedy_refs[make_engine.func] = (
            prompt, _greedy(make_engine(), prompt).output_ids)
    return _greedy_refs[make_engine.func]


def _first_new(ref, at):
    """The index nearest ``at`` from below whose token occurs nowhere
    before it in ``ref``: a stop on that token ends the run exactly there."""
    return next(i for i in range(at, -1, -1) if ref[i] not in ref[:i])


def stop_tokens(make_engine, cfg, params):
    prompt, ref = _reference(make_engine, cfg)
    k = _first_new(ref, 3)
    out = _greedy(make_engine(stop_token_ids=[ref[k]]), prompt)
    assert out.finish_reason == "stop"
    assert out.output_ids == ref[: k + 1]       # the stop token included


def per_request_stop_tokens(make_engine, cfg, params):
    prompt = _prompt(cfg)
    eng = make_engine()         # no global stop
    ref = _greedy(eng, prompt).output_ids
    k = _first_new(ref, 2)
    eng.submit(GenRequest(
        rid="a", input_ids=prompt, max_new_tokens=N_NEW, greedy=True,
        stop_token_ids=[ref[k]]))
    eng.submit(GenRequest(
        rid="b", input_ids=prompt, max_new_tokens=N_NEW, greedy=True))
    outs = {o.rid: o for o in eng.run_until_done(decode_steps=STEPS)}
    assert outs["a"].finish_reason == "stop"
    assert outs["a"].output_ids == ref[: k + 1]
    assert outs["b"].finish_reason == "length"
    assert outs["b"].output_ids == ref


def min_new_tokens_suppresses_stop(make_engine, cfg, params):
    prompt, ref = _reference(make_engine, cfg)
    k = _first_new(ref, 1)      # would stop here without suppression
    out = _greedy(
        make_engine(stop_token_ids=[ref[k]]), prompt, min_new_tokens=k + 3)
    # generation runs on to a later occurrence of the token or to the cap
    assert len(out.output_ids) >= k + 3
    assert out.output_ids == ref[: len(out.output_ids)]
    assert out.finish_reason == (
        "stop" if out.output_ids[-1] == ref[k] else "length")


def interrupt_and_resume_protocol(make_engine, cfg, params):
    """Pause mid-generation, resubmit with the accumulated tokens (the
    partial-rollout protocol): the pieces are the uninterrupted run."""
    prompt, ref = _reference(make_engine, cfg)
    eng = make_engine()
    eng.submit(GenRequest(
        rid="a", input_ids=prompt, max_new_tokens=N_NEW, greedy=True))
    eng.step(decode_steps=STEPS)
    (part,) = eng.pause()
    assert part.finish_reason == "interrupted"
    got = part.output_ids
    assert 0 < len(got) < N_NEW
    eng.resume()
    out = _greedy(eng, prompt + got, N_NEW - len(got), rid="a2")
    assert got + out.output_ids == ref


def update_params_tags_version(make_engine, cfg, params):
    eng = make_engine()
    prompt = _prompt(cfg, 2 * eng.page + 1)
    assert _greedy(eng, prompt, 2, rid="a").version == 0
    assert len(eng.prefix) > 0
    eng.update_params(jax.tree.map(lambda x: x * 0.5, params), version=3)
    assert len(eng.prefix) == 0     # KV of the old weights seeds nothing
    assert _greedy(eng, prompt, 2, rid="b").version == 3


def sampling_reproducible_and_diverse(make_engine, cfg, params):
    runs = []
    for _ in range(2):
        eng = make_engine()
        for i in range(eng.B):
            eng.submit(GenRequest(
                rid=f"s{i}", input_ids=_prompt(cfg, 3), max_new_tokens=8,
                temperature=1.0, top_p=0.95))
        runs.append({
            o.rid: o.output_ids
            for o in eng.run_until_done(decode_steps=STEPS)})
    assert runs[0] == runs[1]                   # one seed, one stream
    assert len(set(map(tuple, runs[0].values()))) > 1   # slots differ


def continuous_batching_slot_turnover(make_engine, cfg, params):
    """More requests than slots: each comes out as it does alone."""
    eng = make_engine()
    rng = np.random.default_rng(1)
    prompts = {
        f"r{i}": _prompt(cfg, int(n), seed=10 + i)
        for i, n in enumerate(rng.integers(3, 9, size=2 * eng.B + 1))
    }
    alone = {
        rid: _greedy(eng, p, 6, rid=rid).output_ids
        for rid, p in prompts.items()}
    for rid, p in prompts.items():
        eng.submit(GenRequest(
            rid=rid, input_ids=p, max_new_tokens=6, greedy=True))
    outs = {o.rid: o.output_ids for o in eng.run_until_done(decode_steps=STEPS)}
    assert outs == alone


def step_harvest_batches_device_pulls(make_engine, cfg, params):
    """``step`` makes at most TWO device pulls a chunk (the per-slot flags,
    one batched fetch of every finished slot's outputs), however many
    slots finish inside the chunk, and no per-slot scatter back."""
    eng = make_engine()
    want = {f"r{i}": 3 + 3 * i for i in range(eng.B)}     # staggered
    for i, (rid, n_new) in enumerate(want.items()):
        eng.submit(GenRequest(
            rid=rid, input_ids=_prompt(cfg, 5, seed=i), max_new_tokens=n_new,
            greedy=True))
    calls = []
    real_get = jax.device_get
    outs = []
    with mock.patch.object(
            jax, "device_get", lambda x: calls.append(1) or real_get(x)):
        for _ in range(40):
            calls.clear()
            outs.extend(eng.step(decode_steps=STEPS))
            assert len(calls) <= 2, f"{len(calls)} device pulls in one step"
            if eng.free_slots() == eng.B and not eng.n_pending():
                break
    assert {o.rid: len(o.output_ids) for o in outs} == want


def pipelined_matches_unpipelined_greedy(make_engine, cfg, params):
    outs = []
    for pipelined in (False, True):
        eng = make_engine(pipeline_chunks=pipelined)
        for i, n in enumerate((5, 9, 3, 7)):
            eng.submit(GenRequest(
                rid=f"r{i}", input_ids=_prompt(cfg, n, seed=20 + i),
                max_new_tokens=10 + i, greedy=True))
        outs.append({o.rid: o for o in eng.run_until_done(decode_steps=STEPS)})
    assert set(outs[0]) == set(outs[1])
    for rid, o in outs[0].items():
        assert o.output_ids == outs[1][rid].output_ids, rid
        assert o.finish_reason == outs[1][rid].finish_reason
        np.testing.assert_allclose(
            o.output_logprobs, outs[1][rid].output_logprobs, atol=1e-5)


def pause_classifies_unharvested_finishes(make_engine, cfg, params):
    """A slot that FINISHED in the in-flight chunk comes out of ``pause``
    as stop or length, not ``interrupted`` (a client would resubmit a
    complete sample)."""
    eng = make_engine(pipeline_chunks=True)
    eng.submit(GenRequest(
        rid="short", input_ids=_prompt(cfg, 3), max_new_tokens=2,
        greedy=True))
    eng.submit(GenRequest(
        rid="long", input_ids=_prompt(cfg, 3, seed=1), max_new_tokens=24,
        greedy=True))
    # one step dispatches a chunk; "short" finishes ON DEVICE inside it
    # and its harvest is deferred (pipelined)
    assert eng.step(decode_steps=STEPS) == []
    assert eng.has_inflight
    harvested = {o.rid: o for o in eng.pause()}
    assert harvested["short"].finish_reason == "length"
    assert len(harvested["short"].output_ids) == 2
    assert harvested["long"].finish_reason == "interrupted"


_CHECKS = (
    stop_tokens,
    per_request_stop_tokens,
    min_new_tokens_suppresses_stop,
    interrupt_and_resume_protocol,
    update_params_tags_version,
    sampling_reproducible_and_diverse,
    continuous_batching_slot_turnover,
    step_harvest_batches_device_pulls,
    pipelined_matches_unpipelined_greedy,
    pause_classifies_unharvested_finishes,
)
CHECKS = [f.__name__ for f in _CHECKS]


def run(check: str, make_engine, cfg, params):
    """Run the check named ``check``; ``make_engine`` is a
    ``functools.partial`` of the family's engine builder over its
    parameters."""
    dict(zip(CHECKS, _CHECKS))[check](make_engine, cfg, params)
