"""Trinity-Mini (family ``afmoe``) through the generation engine's paged
path, against its plain reference (``test_afmoe.py`` has the model, its
tolerance and its sizes: 2 dense + 6 expert layers, window 8, a period of
three window layers and a full one counted over the MODEL's layers).

What the family forces on the engine is nothing new in kind (ONE pool and
one free list, a page table a position of the period, a window position's
pages released behind the window WHILE the request runs, the shared-prefix
plan on the full kind), but it is the first model whose tables' positions
span two weight stacks: positions 0 and 1 of the first period are dense
layers, 2 and 3 expert layers, and the grouped kernel is handed an expert
layer's index in ITS stack. Pages are 4 tokens here, so a generation of 30
tokens crosses the window's edge five times.
"""

import functools

import numpy as np
import pytest

import jax
import jax.numpy as jnp

import engine_contract
from areal_tpu.base import tracing
from areal_tpu.gen.engine import GenerationEngine, GenRequest
from areal_tpu.models import transformer as tfm
from benchmark.reference import afmoe as ref
from test_afmoe import (
    ARCH, CFG, TOL_NATS, _arch, _cfg, _ref_logprobs, _toks, _weights)

PAGE = 4


@pytest.fixture(scope="module")
def params():
    return _weights(CFG)


@pytest.fixture()
def rng():
    return np.random.default_rng(7)


def _engine(params, cfg=CFG, **kw):
    kw.setdefault("max_slots", 4)
    kw.setdefault("max_seqlen", 128)
    kw.setdefault("max_new_tokens_cap", 64)
    kw.setdefault("page_size", PAGE)
    kw.setdefault("admit_buckets", (1, 2, 4))
    return GenerationEngine(cfg, params, **kw)


@pytest.mark.parametrize("check", engine_contract.CHECKS)
def test_engine_contract(params, check):
    engine_contract.run(check, functools.partial(_engine, params), CFG, params)


def _check_outputs(params, prompts, outs, n_new, arch=ARCH):
    for rid, p in prompts.items():
        o = outs[rid]
        assert len(o.output_ids) == n_new, rid
        want = _ref_logprobs(params, p + o.output_ids, arch)[len(p) - 1:]
        np.testing.assert_allclose(
            np.asarray(o.output_logprobs), want, atol=TOL_NATS, err_msg=rid)


def _chunk_attrs():
    return [s["attrs"] for s in tracing.drain()
            if s["name"] == "gen_engine/chunk" and "slots" in s["attrs"]]


def test_pool_has_a_period_axis_and_a_table_a_kind(params):
    """The periods run over the MODEL's layers: two periods for 2 dense + 6
    expert layers, a table a position, three window kinds and one full."""
    eng = _engine(params, n_pages=96)
    # [periods, P, K|V, Hkv, page, D]
    assert eng.state.cache.pages.shape == (2, 96, 2, 2, PAGE, 16)
    assert eng._tables_host.shape == (4, 4, 32)
    assert eng._window_claim == [11, 11, 11, None]
    one = 2 * 2 * 2 * 16 * 4        # a token in one position of the period
    assert eng.cache_bytes_per_token_by_kind() == {
        "full": one, "window": 3 * one}
    assert eng.cache_bytes_per_token() == 4 * one


@pytest.mark.parametrize("use_pallas", [True, None],
                         ids=["interpret_kernel", "xla_gather"])
def test_engine_logprobs_match_reference_past_the_window(
        params, rng, use_pallas):
    """Prefill, then decode through the paged cache FAR past the window of
    8, across the dense and the expert stack (``paged_decode_window`` and
    ``paged_decode`` in interpret mode, and the XLA path): the served
    log-probs are the reference's full forward on prompt + output, while
    the window kinds' pages go back to the free list as the rows move on,
    and the chunk span carries this model's counts as SmallThinker's and
    OLMoE's do."""
    eng = _engine(params, n_pages=96)
    eng._decode_use_pallas = use_pallas
    prompts = {f"r{i}": _toks(rng, n) for i, n in enumerate((3, 14))}
    tracing.drain()
    for rid, p in prompts.items():
        eng.submit(GenRequest(rid=rid, input_ids=p, max_new_tokens=22,
                              temperature=1.0))
    outs = {o.rid: o for o in eng.run_until_done(4)}
    _check_outputs(params, prompts, outs, 22)
    chunks = _chunk_attrs()
    assert sum(c["window_pages_released"] for c in chunks) > 0
    assert eng.stats["window_pages_released"] >= 2 * 3 * 3
    assert all(c["window_resident_tokens"] <= c["resident_tokens"]
               for c in chunks)
    assert any(c["window_resident_tokens"] < c["resident_tokens"]
               for c in chunks)
    assert all(c["cache_bytes_per_token_window"]
               == 3 * c["cache_bytes_per_token_full"] for c in chunks)
    # the experts' census counts the six EXPERT layers, not the dense two
    assert all(c["moe_expert_slots"] == 6 * 8 * c["steps"] for c in chunks)
    assert all(0 < c["moe_experts_hit"] <= c["moe_expert_slots"]
               for c in chunks)
    assert all(("kernel_positions" in c) == bool(use_pallas) for c in chunks)
    if use_pallas:
        assert all("kv_pages_read" in c and "kv_pages_named" in c
                   for c in chunks)
    assert eng.pool.reserved == 0


@pytest.mark.parametrize("prefix_cache", [True, False])
def test_a_prompt_longer_than_the_window_is_admitted_in_chunks(
        params, rng, prefix_cache):
    """61 tokens of prompt through chunks of one page (4): a window kind
    gives its pages back as the chunks pass (counted on the admit span) and
    takes the later ones from its reservation; prefill + decode still equal
    the reference."""
    eng = _engine(params, n_pages=96, enable_prefix_cache=prefix_cache)
    prompts = {"long": _toks(rng, 61), "short": _toks(rng, 6)}
    tracing.drain()
    for rid, p in prompts.items():
        eng.submit(GenRequest(rid=rid, input_ids=p, max_new_tokens=10,
                              temperature=1.0))
    outs = {o.rid: o for o in eng.run_until_done(4)}
    _check_outputs(params, prompts, outs, 10)
    admits = [s["attrs"] for s in tracing.drain()
              if s["name"] == "gen_engine/admit"]
    assert sum(a.get("window_pages_released", 0) for a in admits) >= 3 * 12
    assert eng.pool.reserved == 0
    if not prefix_cache:
        assert eng.pool.n_free == 96


def test_prefix_hit_equals_cold_prefill_in_every_kind(params, rng):
    """The same 27-token prompt first cold, then as a prefix hit (six whole
    pages of every kind shared, the tail prefilled): greedy gives the same
    tokens, and sampled siblings' log-probs are the reference's."""
    eng = _engine(params, n_pages=96)
    prompt = _toks(rng, 27)
    runs = []
    for k in range(2):
        eng.submit(GenRequest(rid=f"g{k}", input_ids=prompt,
                              max_new_tokens=12, greedy=True))
        (o,) = eng.run_until_done(4)
        runs.append(o)
    assert eng.stats["prefix_hit_tokens"] == 24
    assert runs[0].output_ids == runs[1].output_ids
    for k in range(2):
        eng.submit(GenRequest(rid=f"s{k}", input_ids=prompt,
                              max_new_tokens=12, temperature=1.0))
    outs = {o.rid: o for o in eng.run_until_done(4)}
    assert eng.stats["prefix_hit_tokens"] == 3 * 24
    _check_outputs(params, {"s0": prompt, "s1": prompt}, outs, 12)
    # the registry files the page of every kind for a page of prompt
    (node,) = eng.prefix._children.values()
    assert len(node.page) == 4


def test_released_pages_are_reused_by_another_slot_and_nothing_changes(
        params, rng):
    """A pool too small for two requests of 44 positions in a one-kind
    pool (2 x 11 pages x 4 kinds = 88 > 72): the second is admitted only
    because a window kind reserves its window and not its whole output,
    and while both run, pages that one gave up behind its window turn up
    in the other's tables. Every log-prob is still the reference's."""
    eng = _engine(params, n_pages=72, max_slots=2, enable_prefix_cache=False)
    prompts = {"a": _toks(rng, 5), "b": _toks(rng, 7)}
    for rid, p in prompts.items():
        eng.submit(GenRequest(rid=rid, input_ids=p, max_new_tokens=38,
                              temperature=1.0))
    given_up, reused, outs = set(), set(), {}
    before = eng._tables_host[:3, :, :].copy()
    held = eng._held[:3].copy()
    while eng.n_running() or eng.n_pending():
        outs.update({o.rid: o for o in eng.step(4)})
        assert eng.pool.n_unpromised >= 0
        now, now_held = eng._tables_host[:3], eng._held[:3]
        given_up |= set(before[held & ~now_held].tolist())
        reused |= given_up & set(now[now_held & ~held].tolist())
        before, held = now.copy(), now_held.copy()
    assert eng.stats["admitted"] == 2 and len(reused) >= 3
    _check_outputs(params, prompts, outs, 38)
    assert eng.pool.n_free == 72 and eng.pool.reserved == 0


def test_a_group_s_shared_pages_go_through_the_prefix_program(params, rng):
    """A GRPO group on one prompt through the kernels (interpret mode),
    prefix cache on: the members' full-kind table (position 3 of the
    period: an expert layer in the first period, one in the second) names
    the prompt's whole pages, which go through the prefix program
    (``shared_prefix_applies``: ONE full kind); the served log-probs are
    the reference's."""
    eng = _engine(params, n_pages=96)
    eng._decode_use_pallas = True
    prompt = _toks(rng, 14)
    prompts = {f"g{i}": prompt for i in range(3)}
    prompts["alone"] = _toks(rng, 6)
    tracing.drain()
    # the first member fills the registry, its siblings hit it
    eng.submit(GenRequest(rid="g0", input_ids=prompt, max_new_tokens=6,
                          temperature=1.0))
    outs = {o.rid: o for o in eng.run_until_done(4)}
    for rid, ids in list(prompts.items())[1:]:
        eng.submit(GenRequest(rid=rid, input_ids=ids, max_new_tokens=6,
                              temperature=1.0))
    outs.update((o.rid, o) for o in eng.run_until_done(4))
    _check_outputs(params, prompts, outs, 6)
    chunks = _chunk_attrs()
    assert any(c["kv_shared_rows"] for c in chunks)
    assert all((c["kv_pages_read"] < c["kv_pages_named"])
               == bool(c["kv_shared_rows"]) for c in chunks)


def test_the_recorded_routing_is_the_references(params, rng):
    """``record_routing``: a generated token's experts in each of the SIX
    expert layers (none for the dense two), in the order of the model's
    layers, equal to the reference's on prompt + output as sets."""
    eng = _engine(params, n_pages=96, record_routing=True)
    prompt = _toks(rng, 11)
    eng.submit(GenRequest(rid="r", input_ids=prompt, max_new_tokens=14,
                          temperature=1.0))
    (o,) = eng.run_until_done(4)
    got = np.asarray(o.output_routing)              # [generated, Lx, k]
    assert got.shape == (14, 6, 2)
    want = ref.routing(params, ARCH, prompt + o.output_ids)
    want = want[:, len(prompt) - 1: -1].transpose(1, 0, 2)
    np.testing.assert_array_equal(np.sort(got, -1), np.sort(want, -1))


def test_engine_serves_the_same_tokens_through_the_grouped_kernel(
        params, rng, check_moe_grouped_serves_the_same):
    """The routed experts on the einsums and on ``moe_grouped``: the kernel
    is handed the expert stack whole and a layer's index in ITS stack
    (``_routed_at``: the layer's place in the model less the dense
    layers), also for the two expert layers that complete the first
    period; the same greedy tokens, and the two counters add up over the
    six expert layers (``conftest.py``)."""
    prompts = [_toks(rng, n) for n in (5, 19, 33)]
    check_moe_grouped_serves_the_same(lambda: _engine(params), prompts)


def test_an_expert_layers_index_in_its_stack():
    """``_routed_at`` from the running period and the position: model
    layer ``li * 4 + j`` less the two dense layers."""
    stacks = object()
    at = lambda li, j: tfm._routed_at(CFG, stacks, li, j)   # noqa: E731
    assert [at(0, 2)[1], at(0, 3)[1], at(1, 0)[1], at(1, 3)[1]] == [0, 1, 2, 5]
    assert at(1, 1)[0] is stacks and tfm._routed_at(CFG, None, 0, 2) is None


def test_ten_expert_layers_and_two_scanned_periods(rng):
    """2 dense + 10 expert layers: the scan runs TWO whole periods of the
    expert stack behind the four layers run one by one, three periods of
    pages; past the window."""
    arch = _arch(12)
    cfg = _cfg(arch)
    p = _weights(cfg, 9)
    eng = _engine(p, cfg, n_pages=96)
    assert eng.state.cache.pages.shape[0] == 3
    prompts = {"a": _toks(rng, 13), "b": _toks(rng, 4)}
    for rid, ids in prompts.items():
        eng.submit(GenRequest(rid=rid, input_ids=ids, max_new_tokens=14,
                              temperature=1.0))
    outs = {o.rid: o for o in eng.run_until_done(4)}
    _check_outputs(p, prompts, outs, 14, arch)
    assert eng.stats["window_pages_released"] > 0


def test_decode_step_names_its_scopes(params):
    """The gate runs under ``attn_gate`` in every layer of both stacks
    (the dense prologue among them), beside ``moe_experts`` and
    ``moe_shared_expert`` in the expert layers and the kinds' attention
    scopes: by name in the lowered decode step."""
    cache = jax.eval_shape(lambda: tfm.PagedKVCache.empty(CFG, 12, PAGE))
    i32 = lambda *shape: jax.ShapeDtypeStruct(shape, jnp.int32)  # noqa: E731
    text = jax.jit(lambda p, c, t, tb, ln, a: tfm.decode_step_paged(
        p, CFG, c, t, tb, ln, a, use_pallas=False)[:3]).lower(
            params, cache, i32(2), i32(4, 2, 4), i32(2),
            jax.ShapeDtypeStruct((2,), jnp.bool_)).as_text(debug_info=True)
    for scope in ("attn_gate", "moe_experts", "moe_shared_expert",
                  "attn_window", "attn_full"):
        assert scope in text, scope
