"""Serving-gateway tests (docs/serving.md).

End-to-end OpenAI-compatible serving against a REAL (tiny) generation
engine: buffered + SSE completions through the gateway, chunk ordering,
early-disconnect slot release, per-tenant rate limits, KV-occupancy
admission control, weighted-fair-queue starvation freedom, the gen
server's /generate validation 400s, the streaming client, and the
autoscaler decision table on synthetic ``fleet/`` aggregates.
"""

import asyncio
import json
import time

import aiohttp
import pytest

import jax

from areal_tpu.base import metrics as metrics_mod
from areal_tpu.base import network
from areal_tpu.gateway.api import (
    ByteFallbackCodec,
    GatewayConfig,
    GatewayServer,
    serve_gateway,
)
from areal_tpu.gateway.autoscaler import (
    Autoscaler,
    AutoscalerConfig,
    ScaleSignals,
    decide,
)
from areal_tpu.gateway.brownout import (
    BrownoutConfig,
    BrownoutController,
)
from areal_tpu.gateway.brownout import decide as brownout_decide
from areal_tpu.gateway.qos import TenantSpec, TokenBucket, WeightedFairQueue
from areal_tpu.gateway.scheduler import (
    ContinuousBatchScheduler,
    GatewayRequest,
    RateLimited,
    ServiceUnavailable,
)
from areal_tpu.gen.client import DeadlineExceeded, GenAPIClient
from areal_tpu.gen.engine import GenerationEngine, GenRequest
from areal_tpu.gen.server import serve
from areal_tpu.models import transformer as tfm
from areal_tpu.models.config import ModelConfig

CFG = ModelConfig(
    n_layers=2, n_q_heads=4, n_kv_heads=2, head_dim=8, hidden_dim=32,
    intermediate_dim=64, vocab_size=128, dtype="float32",
)


@pytest.fixture(scope="module")
def params():
    return tfm.init_params(CFG, jax.random.key(5))


class _Stack:
    """Engine + gen server + scheduler + gateway on real TCP ports."""

    def __init__(self, eng, gen_runner, scheduler, gw_runner, gw_url):
        self.eng = eng
        self.gen_runner = gen_runner
        self.scheduler = scheduler
        self.gw_runner = gw_runner
        self.gw_url = gw_url

    async def close(self):
        await self.scheduler.stop()
        await self.gw_runner.cleanup()
        await self.gen_runner.cleanup()


async def _stack(
    params, *, slots=4, tenants=None, max_queue=64, decode_steps=2,
    gw_config=None, metrics_poll_interval=2.0,
) -> _Stack:
    eng = GenerationEngine(CFG, params, max_slots=slots, max_seqlen=128)
    gen_port = network.find_free_port()
    gen_runner = await serve(
        eng, "127.0.0.1", gen_port, decode_steps=decode_steps
    )
    scheduler = ContinuousBatchScheduler(
        [f"http://127.0.0.1:{gen_port}"],
        tenants or {},
        max_queue=max_queue,
        metrics_poll_interval=metrics_poll_interval,
    )
    await scheduler.start()
    gw = GatewayServer(
        scheduler, ByteFallbackCodec(CFG.vocab_size),
        gw_config or GatewayConfig(max_tokens_cap=256),
    )
    gw_port = network.find_free_port()
    gw_runner = await serve_gateway(gw, "127.0.0.1", gw_port)
    return _Stack(
        eng, gen_runner, scheduler, gw_runner,
        f"http://127.0.0.1:{gw_port}",
    )


async def _sse_frames(resp):
    frames, done = [], False
    async for raw in resp.content:
        line = raw.strip()
        if not line.startswith(b"data:"):
            continue
        payload = line[len(b"data:"):].strip()
        if payload == b"[DONE]":
            done = True
            break
        frames.append(json.loads(payload))
    return frames, done


PROMPT = [3, 17, 42, 99, 5]


# --------------------------------------------------------------------- #
# OpenAI surface, end to end against the real engine
# --------------------------------------------------------------------- #


async def test_completion_e2e_buffered_and_streaming(params):
    st = await _stack(params)
    try:
        async with aiohttp.ClientSession() as s:
            r = await s.post(
                f"{st.gw_url}/v1/completions",
                json={"prompt": PROMPT, "max_tokens": 8, "temperature": 0},
            )
            assert r.status == 200, await r.text()
            body = await r.json()
            assert body["object"] == "text_completion"
            choice = body["choices"][0]
            assert choice["finish_reason"] in ("stop", "length")
            assert body["usage"]["completion_tokens"] == 8
            assert body["usage"]["prompt_tokens"] == len(PROMPT)
            buffered_text = choice["text"]
            assert len(buffered_text) > 0

            # same greedy prompt, streamed: the concatenated deltas must
            # equal the buffered text, finish_reason only on the last
            # frame, [DONE] terminator present
            r = await s.post(
                f"{st.gw_url}/v1/completions",
                json={
                    "prompt": PROMPT, "max_tokens": 8, "temperature": 0,
                    "stream": True,
                },
            )
            assert r.status == 200
            assert r.headers["Content-Type"].startswith("text/event-stream")
            frames, done = await _sse_frames(r)
            assert done
            assert len(frames) >= 2  # decode_steps=2 < 8 tokens -> chunks
            for f in frames[:-1]:
                assert f["choices"][0]["finish_reason"] is None
            assert frames[-1]["choices"][0]["finish_reason"] in (
                "stop", "length"
            )
            streamed = "".join(f["choices"][0]["text"] for f in frames)
            assert streamed == buffered_text
    finally:
        await st.close()


async def test_chat_completion_e2e(params):
    st = await _stack(params)
    try:
        async with aiohttp.ClientSession() as s:
            msgs = [
                {"role": "system", "content": "hi"},
                {"role": "user", "content": "abc"},
            ]
            r = await s.post(
                f"{st.gw_url}/v1/chat/completions",
                json={"messages": msgs, "max_tokens": 6, "temperature": 0},
            )
            assert r.status == 200, await r.text()
            body = await r.json()
            assert body["object"] == "chat.completion"
            msg = body["choices"][0]["message"]
            assert msg["role"] == "assistant"
            assert isinstance(msg["content"], str)

            r = await s.post(
                f"{st.gw_url}/v1/chat/completions",
                json={
                    "messages": msgs, "max_tokens": 6, "temperature": 0,
                    "stream": True,
                },
            )
            frames, done = await _sse_frames(r)
            assert done and frames
            assert frames[0]["object"] == "chat.completion.chunk"
            assert frames[0]["choices"][0]["delta"].get("role") == "assistant"
    finally:
        await st.close()


async def test_gateway_validation_400(params):
    st = await _stack(params)
    bad_bodies = [
        {},                                             # missing prompt
        {"prompt": ""},                                 # empty prompt
        {"prompt": PROMPT, "max_tokens": 0},            # max_tokens < 1
        {"prompt": PROMPT, "temperature": -1},          # bad temperature
        {"prompt": PROMPT, "top_p": 0},                 # bad top_p
        {"prompt": PROMPT, "n": 2},                     # unsupported n
        {"prompt": [1.5, 2.5]},                         # non-int tokens
        {"prompt": PROMPT, "stop_token_ids": 5},        # non-list stops
        {"prompt": PROMPT, "max_tokens": 256},          # beyond slot cap
    ]
    try:
        async with aiohttp.ClientSession() as s:
            for body in bad_bodies:
                r = await s.post(f"{st.gw_url}/v1/completions", json=body)
                assert r.status == 400, body
                err = (await r.json())["error"]
                assert err["type"] == "invalid_request_error"
            r = await s.post(
                f"{st.gw_url}/v1/chat/completions", json={"messages": []}
            )
            assert r.status == 400
            # tenancy: unknown key with require_api_key=False falls back
            # to anonymous and still serves
            r = await s.post(
                f"{st.gw_url}/v1/completions",
                json={"prompt": PROMPT, "max_tokens": 2},
                headers={"Authorization": "Bearer nope"},
            )
            assert r.status == 200
    finally:
        await st.close()


# --------------------------------------------------------------------- #
# QoS: rate limits, fair queueing, admission control
# --------------------------------------------------------------------- #


async def test_per_tenant_rate_limit_enforced(params):
    # tenant "small" can afford exactly one request (burst == one cost);
    # tenant "big" is unlimited and must be unaffected
    cost = len(PROMPT) + 4
    tenants = {
        "small": TenantSpec(
            "small", rate_tokens_per_s=0.001, burst_tokens=cost
        ),
        "big": TenantSpec("big"),
    }
    st = await _stack(params, tenants=tenants)
    try:
        async with aiohttp.ClientSession() as s:
            body = {"prompt": PROMPT, "max_tokens": 4, "temperature": 0}
            r = await s.post(
                f"{st.gw_url}/v1/completions", json=body,
                headers={"X-Tenant": "small"},
            )
            assert r.status == 200
            r = await s.post(
                f"{st.gw_url}/v1/completions", json=body,
                headers={"X-Tenant": "small"},
            )
            assert r.status == 429
            assert "Retry-After" in r.headers
            assert (await r.json())["error"]["code"] == "rate_limit_exceeded"
            # the heavy-handed tenant's limit is not the fleet's
            r = await s.post(
                f"{st.gw_url}/v1/completions", json=body,
                headers={"X-Tenant": "big"},
            )
            assert r.status == 200
    finally:
        await st.close()


async def test_unserveable_cost_answers_400_not_429(params):
    # cost above burst can NEVER be admitted: a 429 would retry forever
    tenants = {"tiny": TenantSpec("tiny", rate_tokens_per_s=1.0,
                                  burst_tokens=4.0)}
    st = await _stack(params, tenants=tenants)
    try:
        async with aiohttp.ClientSession() as s:
            r = await s.post(
                f"{st.gw_url}/v1/completions",
                json={"prompt": PROMPT, "max_tokens": 50},
                headers={"X-Tenant": "tiny"},
            )
            assert r.status == 400
            assert "never be admitted" in (await r.json())["error"]["message"]
    finally:
        await st.close()


async def test_unknown_x_tenant_collapses_to_default(params):
    # rotating X-Tenant must not mint fresh token buckets per name
    st = await _stack(params)
    try:
        async with aiohttp.ClientSession() as s:
            for i in range(3):
                r = await s.post(
                    f"{st.gw_url}/v1/completions",
                    json={"prompt": PROMPT, "max_tokens": 2},
                    headers={"X-Tenant": f"minted-{i}"},
                )
                assert r.status == 200
        assert not any(
            t.startswith("minted-") for t in st.scheduler.tenants
        )
    finally:
        await st.close()


def test_wfq_drop_rolls_back_virtual_clock():
    # cancelled queued work must not deprioritize the tenant's future
    # traffic: after dropping its whole backlog, its next item competes
    # as if the backlog never existed
    q = WeightedFairQueue()
    for i in range(10):
        q.push("a", 100.0, 1.0, ("a", i))
    q.push("b", 150.0, 1.0, ("b", 0))
    q.drop_where(lambda it: it[0] == "a")
    q.push("a", 100.0, 1.0, ("a", "fresh"))
    # a's rolled-back stamp (100) beats b's (150); without the rollback
    # a's stamp would be 1100 and b would pop first
    assert q.pop() == ("a", "fresh")


def test_wfq_rollback_after_pop():
    # the popped-entry twin of drop_where's rollback: a popped-then-
    # cancelled request must not deprioritize the tenant's future traffic
    q = WeightedFairQueue()
    q.push("a", 100.0, 1.0, ("a", 0))
    q.push("a", 100.0, 1.0, ("a", 1))
    assert q.pop() == ("a", 0)
    q.rollback("a", 100.0, 1.0)
    # the tenant's clock holds only the SURVIVING entry's share, and that
    # entry's stamp shifted down with it
    assert q._last_vft["a"] == pytest.approx(100.0)
    assert q._queues["a"][0][0] == pytest.approx(100.0)
    assert q.pop() == ("a", 1)


def test_demand_occupancy_excludes_evictable_cache(params):
    # a cache-warm idle server must not read as "full" to the admission
    # gate: raw occupancy counts prefix-cache pages the next admission
    # would evict; the demand signal excludes them
    eng = GenerationEngine(CFG, params, max_slots=2, max_seqlen=512)
    prompt = list(range(1, 128)) + [5, 9, 11]  # > one page: cacheable
    eng.submit(GenRequest(rid="a", input_ids=prompt, max_new_tokens=2,
                          greedy=True))
    eng.run_until_done(decode_steps=2)
    assert eng.n_running() == 0
    assert eng.kv_pool_occupancy() > 0.0          # cache holds pages
    assert eng.kv_pool_demand_occupancy() == 0.0  # all reclaimable


class _StubGenClient:
    """Capacity-poll-only stand-in: the dispatch path must never reach
    generate_stream in the cancel-race test."""

    def __init__(self):
        self.streams = 0

    async def metrics(self, url):
        return {
            "max_slots": 4,
            "kv_pool_demand_occupancy": 0.0,
            "slot_capacity": 4096,
        }

    async def generate_stream(self, url, rid, ids, sp, deadline_s=None):
        self.streams += 1
        yield {"token_ids": [], "logprobs": [], "finish_reason": "stop"}


async def test_cancel_while_dispatching_refunds_charge():
    """cancel() racing the dispatch pop: drop_where misses the popped
    entry and no _run_request will ever settle it — the dispatch loop
    must refund the full budget or the tenant bucket leaks one request
    cost per race (lifecycle-rule triage fix)."""
    stub = _StubGenClient()
    sched = ContinuousBatchScheduler(
        ["http://stub:1"],
        tenants={"t": TenantSpec(
            name="t", weight=1.0, rate_tokens_per_s=100.0,
            burst_tokens=10_000.0,
        )},
        client=stub,
    )
    await sched.start()
    try:
        req = GatewayRequest.build("t", [1, 2, 3], {"max_new_tokens": 61})
        bucket = sched._bucket("t")
        before = bucket.available
        # the race, made deterministic: the flag is set but the entry is
        # (about to be) popped, so cancel()'s drop_where path misses it
        req.cancelled = True
        sched.submit(req)
        assert bucket.available <= before - req.cost + 1.0
        for _ in range(200):
            await asyncio.sleep(0.01)
            if sched.queue_depth() == 0 and sched.inflight() == 0:
                break
        assert sched.queue_depth() == 0
        assert sched.inflight() == 0
        assert stub.streams == 0  # never dispatched to a backend
        assert bucket.available == pytest.approx(before, abs=2.0)
        # the fair-queue virtual clock rolled back too: the popped entry
        # never ran, so it must not count against the tenant's share
        assert sched._wfq._last_vft.get("t", 0.0) == pytest.approx(0.0)
    finally:
        await sched.stop()


def test_token_bucket_refill_and_refund():
    t = {"now": 0.0}
    b = TokenBucket(10.0, 20.0, clock=lambda: t["now"])
    assert b.try_acquire(20.0)
    assert not b.try_acquire(1.0)
    assert b.retry_after_s(1.0) == pytest.approx(0.1)
    t["now"] = 1.0  # 10 tokens refilled
    assert b.try_acquire(10.0)
    b.refund(5.0)
    assert b.try_acquire(5.0)
    # unlimited bucket never rejects
    assert TokenBucket(0.0, 0.0).try_acquire(1e12)


def test_fair_queue_starvation_free():
    q = WeightedFairQueue()
    for i in range(50):
        q.push("heavy", 100.0, 1.0, ("heavy", i))
    q.push("light", 100.0, 1.0, ("light", 0))
    # the light tenant enqueued LAST but its virtual finish time rides the
    # global clock, not the heavy backlog: it must pop within the first 2
    first_two = [q.pop() for _ in range(2)]
    assert ("light", 0) in first_two
    # weighted share: a weight-3 tenant drains ~3x faster than weight-1
    q = WeightedFairQueue()
    for i in range(30):
        q.push("w1", 10.0, 1.0, ("w1", i))
        q.push("w3", 10.0, 3.0, ("w3", i))
    head = [q.pop()[0] for _ in range(20)]
    assert head.count("w3") >= 2 * head.count("w1")


async def test_admission_holds_at_full_kv_pool(params):
    st = await _stack(params, metrics_poll_interval=9999.0)
    try:
        sched = st.scheduler
        srv = next(iter(sched._servers.values()))
        srv.kv_occupancy = 0.99  # full pool: past the admit gate
        req = GatewayRequest.build(
            "t", PROMPT, {"max_new_tokens": 4, "greedy": True}
        )
        sched.submit(req)
        await asyncio.sleep(0.2)
        # queued, NOT dispatched — the engine never sees it
        assert sched.queue_depth() == 1
        assert sched.inflight() == 0
        # pool frees up: dispatch proceeds and the request completes
        srv.kv_occupancy = 0.0
        sched._wake.set()
        got = []
        async for ev in sched.events(req):
            got.extend(ev.get("token_ids", []))
        assert len(got) == 4
        assert sched.queue_depth() == 0
    finally:
        await st.close()


async def test_queue_full_answers_429(params):
    st = await _stack(params, max_queue=1, metrics_poll_interval=9999.0)
    try:
        sched = st.scheduler
        next(iter(sched._servers.values())).kv_occupancy = 0.99  # block
        sched.submit(
            GatewayRequest.build("t", PROMPT, {"max_new_tokens": 2})
        )
        with pytest.raises(RateLimited):
            sched.submit(
                GatewayRequest.build("t", PROMPT, {"max_new_tokens": 2})
            )
    finally:
        await st.close()


# --------------------------------------------------------------------- #
# gen-server satellites: /generate validation, SSE, disconnect, client
# --------------------------------------------------------------------- #


async def test_generate_validation_400(params):
    eng = GenerationEngine(CFG, params, max_slots=2, max_seqlen=128)
    port = network.find_free_port()
    runner = await serve(eng, "127.0.0.1", port, decode_steps=2)
    url = f"http://127.0.0.1:{port}"
    bad = [
        {"input_ids": PROMPT},                                  # no rid
        {"rid": "a", "input_ids": []},                          # empty
        {"rid": "a", "input_ids": ["x"]},                       # non-int
        {"rid": "a", "input_ids": [5, 999]},                    # OOV
        {"rid": "a", "input_ids": PROMPT,
         "sampling_params": {"max_new_tokens": 0}},
        {"rid": "a", "input_ids": PROMPT,
         "sampling_params": {"temperature": -0.5}},
        {"rid": "a", "input_ids": PROMPT,
         "sampling_params": {"top_p": 0.0}},
        {"rid": "a", "input_ids": PROMPT,
         "sampling_params": {"top_k": 0}},
        {"rid": "a", "input_ids": PROMPT,
         "sampling_params": {"min_new_tokens": 9, "max_new_tokens": 4}},
        {"rid": "a", "input_ids": PROMPT,
         "sampling_params": {"max_new_tokens": 4096}},           # capacity
    ]
    try:
        async with aiohttp.ClientSession() as s:
            for body in bad:
                for endpoint in ("/generate", "/generate_stream"):
                    r = await s.post(url + endpoint, json=body)
                    assert r.status == 400, (endpoint, body)
                    assert "error" in await r.json()
            # nothing leaked into the engine
            assert eng.n_running() == 0 and eng.n_pending() == 0
    finally:
        await runner.cleanup()


async def test_generate_stream_client_chunks_match_generate(params):
    eng = GenerationEngine(CFG, params, max_slots=2, max_seqlen=128)
    port = network.find_free_port()
    runner = await serve(eng, "127.0.0.1", port, decode_steps=2)
    url = f"http://127.0.0.1:{port}"
    sp = {"max_new_tokens": 10, "greedy": True}
    try:
        async with GenAPIClient() as c:
            ref = await c.generate(url, "ref", PROMPT, sp)
            toks, lps, finals = [], [], []
            async for ev in c.generate_stream(url, "stream", PROMPT, sp):
                assert len(ev["token_ids"]) == len(ev["logprobs"])
                toks.extend(ev["token_ids"])
                lps.extend(ev["logprobs"])
                if ev.get("finish_reason"):
                    finals.append(ev)
            # chunk-granular deltas concatenate to exactly the buffered
            # result, and exactly one final frame arrives
            assert toks == ref.output_ids
            assert len(finals) == 1
            assert finals[0]["finish_reason"] == ref.finish_reason
            assert finals[0]["version"] == ref.version
    finally:
        await runner.cleanup()


async def test_stream_early_disconnect_releases_slot(params):
    eng = GenerationEngine(CFG, params, max_slots=2, max_seqlen=128)
    port = network.find_free_port()
    runner = await serve(eng, "127.0.0.1", port, decode_steps=2)
    try:
        async with aiohttp.ClientSession() as s:
            resp = await s.post(
                f"http://127.0.0.1:{port}/generate_stream",
                json={
                    "rid": "dc", "input_ids": PROMPT,
                    "sampling_params": {"max_new_tokens": 120,
                                        "greedy": True},
                },
            )
            assert resp.status == 200
            async for raw in resp.content:  # first delta then hang up
                if raw.startswith(b"data:"):
                    break
            resp.close()
        # the server notices the disconnect and frees the slot + pages
        for _ in range(100):
            await asyncio.sleep(0.05)
            if eng.n_running() == 0 and eng.pool.n_free == eng.n_pages:
                break
        assert eng.n_running() == 0
        assert eng.pool.n_free == eng.n_pages
    finally:
        await runner.cleanup()


# --------------------------------------------------------------------- #
# autoscaler decision table (synthetic fleet/ aggregates)
# --------------------------------------------------------------------- #


def _signals(**kw):
    base = dict(routed=4, healthy=4, queue_depth=0.0, kv_occupancy=0.1,
                queue_wait_p95_s=0.0, breaker_open=0)
    base.update(kw)
    return ScaleSignals(**base)


def test_autoscaler_decision_table():
    cfg = AutoscalerConfig(min_servers=2, max_servers=8)
    cases = [
        # (signals, expected action, expected delta)
        (_signals(routed=1, healthy=1), "grow", 1),          # below floor
        (_signals(healthy=3, breaker_open=1), "grow", 1),    # replace open
        (_signals(queue_depth=40.0), "grow", 2),             # deep backlog
        (_signals(queue_depth=17.0), "grow", 1),             # mild backlog
        (_signals(kv_occupancy=0.9), "grow", 1),             # HBM pressure
        (_signals(queue_wait_p95_s=30.0), "grow", 1),        # latency
        (_signals(), "shrink", 1),                           # idle
        (_signals(routed=2, healthy=2), "hold", 0),          # at the floor
        (_signals(queue_depth=8.0), "hold", 0),              # loaded but ok
        (_signals(routed=8, healthy=8, queue_depth=100.0),
         "hold", 0),                                         # at the ceiling
    ]
    for sig, action, delta in cases:
        d = decide(cfg, sig)
        assert d.action == action, (sig, d)
        if action != "hold":
            assert d.delta == delta, (sig, d)
        if d.action != "hold":
            assert d.reasons


def test_autoscaler_signals_from_fleet_scalars():
    scalars = {
        "gw_queue_depth": 12.0,
        "kv_pool_occupancy": 1.8,      # gauge SUM over 2 gen servers
        "gw/queue_wait_s/p95": 3.5,
        "servers_total": 2.0,
        "servers_open": 1.0,
        "servers_half_open": 0.0,
    }
    sig = ScaleSignals.from_fleet_scalars(scalars, routed=2)
    assert sig.queue_depth == 12.0
    assert sig.kv_occupancy == pytest.approx(0.9)
    assert sig.queue_wait_p95_s == 3.5
    assert sig.breaker_open == 1
    assert sig.healthy == 1


def test_autoscaler_cooldown_and_callbacks():
    t = {"now": 0.0}
    sig = {"cur": _signals(queue_depth=100.0)}
    grown, shrunk = [], []
    asc = Autoscaler(
        AutoscalerConfig(min_servers=1, max_servers=8, cooldown_s=30.0),
        fetch_signals=lambda: sig["cur"],
        grow_cb=lambda n: grown.append(n) or n,
        shrink_cb=lambda n: shrunk.append(n) or n,
        clock=lambda: t["now"],
    )
    d = asc.step_once()
    assert d.action == "grow" and grown == [d.delta]
    # inside the cooldown window further actions are deferred
    t["now"] = 10.0
    assert asc.step_once().action == "hold"
    # after the cooldown, an idle fleet shrinks
    t["now"] = 40.0
    sig["cur"] = _signals()
    d = asc.step_once()
    assert d.action == "shrink" and shrunk == [1]


# --------------------------------------------------------------------- #
# survivability: deadlines, hedged dispatch, brownout, 503s
# --------------------------------------------------------------------- #


class _BlockedStubClient:
    """Reports a pinned KV pool so dispatch never proceeds — requests
    stay queued, which is where the deadline sweep must find them."""

    def __init__(self):
        self.streams = 0

    async def metrics(self, url):
        return {
            "max_slots": 4,
            "kv_pool_demand_occupancy": 1.0,
            "slot_capacity": 4096,
        }

    async def generate_stream(self, url, rid, ids, sp, deadline_s=None):
        self.streams += 1
        yield {"token_ids": [], "logprobs": [], "finish_reason": "stop"}


async def test_deadline_expire_in_queue_refunds_and_rolls_back():
    """A queued request whose deadline lapses is shed IN QUEUE: full
    token-bucket refund, fair-clock rollback, a final deadline event for
    the waiting handler — and the backend never sees it."""
    t = {"now": 0.0}
    stub = _BlockedStubClient()
    sched = ContinuousBatchScheduler(
        ["http://stub:1"],
        tenants={"t": TenantSpec(
            name="t", rate_tokens_per_s=100.0, burst_tokens=10_000.0,
        )},
        client=stub,
        clock=lambda: t["now"],
    )
    await sched.start()
    try:
        shed0 = metrics_mod.counters.get(metrics_mod.GW_DEADLINE_SHED)
        bucket = sched._bucket("t")
        before = bucket.available
        req = GatewayRequest.build(
            "t", [1, 2, 3], {"max_new_tokens": 8}, deadline_s=5.0,
        )
        sched.submit(req)
        assert req.deadline_t == pytest.approx(5.0)
        assert bucket.available < before  # charged on admit
        t["now"] = 10.0
        assert sched.sweep_deadlines() == 1
        evs = []
        async for ev in sched.events(req):
            evs.append(ev)
        assert evs[-1]["finish_reason"] == "deadline"
        assert stub.streams == 0          # never dispatched
        assert sched.queue_depth() == 0
        assert bucket.available == pytest.approx(before)
        assert sched._wfq._last_vft.get("t", 0.0) == pytest.approx(0.0)
        assert (
            metrics_mod.counters.get(metrics_mod.GW_DEADLINE_SHED) - shed0
            == 1
        )
    finally:
        await sched.stop()


class _HedgeStubClient:
    """One backend wedges pre-first-chunk, the other streams; records
    every stream open/close so the test can assert the loser was torn
    down and no slot is left bound."""

    def __init__(self, slow_url):
        self.slow_url = slow_url
        self.streams = []
        self.closed = []

    async def metrics(self, url):
        return {
            "max_slots": 4,
            "kv_pool_demand_occupancy": 0.0,
            "slot_capacity": 4096,
        }

    async def generate_stream(self, url, rid, ids, sp, deadline_s=None):
        self.streams.append((url, rid))
        try:
            if url == self.slow_url:
                await asyncio.sleep(3600)
            for _ in range(4):
                yield {"token_ids": [7], "logprobs": [0.0],
                       "finish_reason": None}
                await asyncio.sleep(0.02)
            yield {"token_ids": [], "logprobs": [], "finish_reason": "stop"}
        finally:
            self.closed.append((url, rid))


async def test_cancel_during_hedge_settles_slots_and_bucket():
    """Wedged primary -> the hedge wins; the client then cancels
    mid-stream. Both backends' slot holds must come back, the loser's
    stream must be closed, and the bucket must settle to exactly what
    was consumed — the hedge must never double-charge."""
    metrics_mod.counters.clear(metrics_mod.GW_TTFT_S)
    urls = ["http://a:1", "http://b:1"]
    stub = _HedgeStubClient(slow_url=urls[0])
    sched = ContinuousBatchScheduler(
        list(urls),
        tenants={"t": TenantSpec(
            # near-zero refill so the final balance shows REFUNDS, not
            # the bucket quietly refilling behind the assertion
            name="t", rate_tokens_per_s=0.01, burst_tokens=10_000.0,
        )},
        client=stub,
        hedge_enabled=True,
        hedge_min_delay_s=0.05,
    )
    await sched.start()
    try:
        hedges0 = metrics_mod.counters.get(metrics_mod.GW_HEDGES)
        wins0 = metrics_mod.counters.get(metrics_mod.GW_HEDGE_WINS)
        bucket = sched._bucket("t")
        before = bucket.available
        req = GatewayRequest.build("t", [1, 2, 3], {"max_new_tokens": 64})
        sched.submit(req)
        got = []
        async for ev in sched.events(req):
            got.extend(ev.get("token_ids", []))
            if len(got) >= 2:
                sched.cancel(req)
                break
        for _ in range(300):
            await asyncio.sleep(0.01)
            if sched.inflight() == 0:
                break
        assert sched.inflight() == 0
        assert metrics_mod.counters.get(metrics_mod.GW_HEDGES) - hedges0 == 1
        assert (
            metrics_mod.counters.get(metrics_mod.GW_HEDGE_WINS) - wins0 == 1
        )
        # both backends were opened; the wedged loser was closed
        assert {u for u, _ in stub.streams} == set(urls)
        assert urls[0] in {u for u, _ in stub.closed}
        # bucket settled at cost-of-what-ran, not the full budget and
        # not a double (hedged) charge
        used = 3 + req.n_generated
        assert bucket.available == pytest.approx(before - used, abs=1.0)
    finally:
        await sched.stop()


async def test_all_breakers_open_answers_503_with_retry_after(params):
    """Every backend breaker open: submit raises ServiceUnavailable and
    the HTTP surface turns it into 503 + an honest Retry-After — not a
    silent hang, not a 429 blaming the client."""
    st = await _stack(params, metrics_poll_interval=9999.0)
    try:
        for s in st.scheduler._servers.values():
            s.healthy = False
        with pytest.raises(ServiceUnavailable) as ei:
            st.scheduler.submit(
                GatewayRequest.build("t", PROMPT, {"max_new_tokens": 2})
            )
        assert ei.value.retry_after_s > 0
        async with aiohttp.ClientSession() as s:
            r = await s.post(
                f"{st.gw_url}/v1/completions",
                json={"prompt": PROMPT, "max_tokens": 2},
            )
            assert r.status == 503
            assert int(r.headers["Retry-After"]) >= 1
            err = (await r.json())["error"]
            assert err["code"] == "service_unavailable"
    finally:
        await st.close()


def test_queue_full_retry_after_is_drain_estimate():
    """The queue-full 429 hint tracks the live queue-wait p95 (clamped
    to [1, 60]) instead of a made-up constant."""
    sched = ContinuousBatchScheduler(
        ["http://stub:1"], client=_BlockedStubClient(),
    )
    metrics_mod.counters.clear(metrics_mod.GW_QUEUE_WAIT_S)
    assert sched._queue_retry_after_s() == pytest.approx(1.0)
    for _ in range(20):
        metrics_mod.counters.observe(metrics_mod.GW_QUEUE_WAIT_S, 5.0)
    assert sched._queue_retry_after_s() == pytest.approx(5.0, rel=0.2)
    for _ in range(200):
        metrics_mod.counters.observe(metrics_mod.GW_QUEUE_WAIT_S, 120.0)
    assert sched._queue_retry_after_s() == pytest.approx(60.0)
    metrics_mod.counters.clear(metrics_mod.GW_QUEUE_WAIT_S)


async def test_generate_stream_connect_retries_honor_deadline():
    """Connect retries against a dead backend stop at the request
    deadline with the typed DeadlineExceeded — not after the full
    backoff ladder."""
    from areal_tpu.gen.client import RetryPolicy

    url = f"http://127.0.0.1:{network.find_free_port()}"  # nobody there
    # backoff big enough that the attempt budget alone would outlive the
    # deadline: only the deadline check can end the loop
    async with GenAPIClient(
        timeout=5.0,
        retry=RetryPolicy(max_attempts=100, backoff_base_s=0.5, jitter=0.0),
    ) as cl:
        before = metrics_mod.counters.get(metrics_mod.FT_CLIENT_RETRIES)
        with pytest.raises(DeadlineExceeded):
            async for _ in cl.generate_stream(
                url, "r-dead", [1, 2], {"max_new_tokens": 2},
                deadline_s=0.4,
            ):
                pass
        # counted, not timed: the first backoff (0.5 s) already outlives
        # the 0.4 s left, so the loop ends before ONE of its 99 retries
        assert metrics_mod.counters.get(
            metrics_mod.FT_CLIENT_RETRIES) == before


async def test_deadline_e2e_504_and_validation(params):
    """A request whose budget can't be met answers 504 (its own typed
    error, not a generic 500); a malformed deadline answers 400."""
    st = await _stack(params)
    try:
        async with aiohttp.ClientSession() as s:
            r = await s.post(
                f"{st.gw_url}/v1/completions",
                json={"prompt": PROMPT, "max_tokens": 8,
                      "timeout": 0.0001},
            )
            assert r.status == 504, await r.text()
            err = (await r.json())["error"]
            assert err["code"] == "deadline_exceeded"
            # header spelling of the same deadline
            r = await s.post(
                f"{st.gw_url}/v1/completions",
                json={"prompt": PROMPT, "max_tokens": 8},
                headers={"X-Request-Deadline": "0.0001"},
            )
            assert r.status == 504
            for bad in (-1, "soon", float("inf")):
                r = await s.post(
                    f"{st.gw_url}/v1/completions",
                    json={"prompt": PROMPT, "max_tokens": 2,
                          "timeout": bad},
                )
                assert r.status == 400, bad
            # a generous deadline changes nothing
            r = await s.post(
                f"{st.gw_url}/v1/completions",
                json={"prompt": PROMPT, "max_tokens": 4, "timeout": 300,
                      "temperature": 0},
            )
            assert r.status == 200
            assert (await r.json())["usage"]["completion_tokens"] == 4
    finally:
        await st.close()


def test_brownout_decide_table():
    cfg = BrownoutConfig()

    def sig(**kw):
        return ScaleSignals(routed=4, healthy=4, **kw)

    # healthy fleet holds at 0
    assert brownout_decide(cfg, sig(), 0) == 0
    # each signal kind can trip a rung on its own
    assert brownout_decide(cfg, sig(kv_occupancy=0.91), 0) == 1
    assert brownout_decide(cfg, sig(queue_wait_p95_s=16.0), 0) == 1
    assert brownout_decide(cfg, sig(queue_wait_p95_s=31.0), 0) == 2
    assert brownout_decide(cfg, sig(breaker_open=3), 0) == 2
    # three rungs: clamp, shed, admit nothing, at their thresholds
    assert [(lv.kv_occupancy, lv.queue_wait_p95_s, lv.breaker_open_frac)
            for lv in cfg.levels] == [
        (0.90, 5.0, 0.25), (0.97, 30.0, 0.75), (0.99, 60.0, 1.00)]
    # escalation jumps straight to the worst tripped rung
    assert brownout_decide(cfg, sig(kv_occupancy=0.995), 0) == 3
    assert brownout_decide(cfg, sig(kv_occupancy=0.995), 2) == 3
    # hysteresis: below the entry bound but above entry*h holds the rung
    assert brownout_decide(cfg, sig(kv_occupancy=0.80), 1) == 1
    assert brownout_decide(cfg, sig(kv_occupancy=0.50), 1) == 0
    # de-escalation is one rung at a time even from a silent fleet
    assert brownout_decide(cfg, sig(), 3) == 2


async def test_brownout_controller_dwell_and_levers():
    calls = {"clamp": [], "shed": [], "pause": []}
    t = {"now": 0.0}
    sig = {"s": ScaleSignals(routed=2, healthy=2)}

    cfg = BrownoutConfig(min_hold_s=10.0, interval_s=1.0)
    ctrl = BrownoutController(
        cfg,
        lambda: sig["s"],
        lambda v: calls["clamp"].append(v),
        lambda floor, ra: calls["shed"].append(floor),
        lambda paused, ra: calls["pause"].append(paused),
        clock=lambda: t["now"],
    )
    sig["s"] = ScaleSignals(routed=2, healthy=2, kv_occupancy=0.975)
    assert await ctrl.step_once() == 2
    assert calls["clamp"][-1] == cfg.clamp_max_tokens
    assert calls["shed"][-1] == cfg.weight_floor
    assert calls["pause"][-1] is False
    # recovery is dwell-gated...
    sig["s"] = ScaleSignals(routed=2, healthy=2)
    t["now"] = 5.0
    assert await ctrl.step_once() == 2
    # ...and one rung per pass once the hold lapses
    t["now"] = 20.0
    assert await ctrl.step_once() == 1
    assert calls["shed"][-1] == 0.0
    t["now"] = 40.0
    assert await ctrl.step_once() == 0
    assert calls["clamp"][-1] is None
    # escalation is NEVER dwell-gated
    sig["s"] = ScaleSignals(routed=2, healthy=2, kv_occupancy=0.995)
    t["now"] = 40.5
    assert await ctrl.step_once() == 3
    assert calls["shed"][-1] == cfg.weight_floor
    assert calls["pause"][-1] is True
    # the Retry-After hint is at least one controller interval
    assert ctrl.retry_after_s() >= cfg.interval_s


async def test_brownout_clamp_applies_to_new_requests(params):
    """Level-1 clamp: the gateway caps max_tokens fleet-wide without
    erroring the request — shorter answers, not failures."""
    gw_config = GatewayConfig(max_tokens_cap=256)
    st = await _stack(params, gw_config=gw_config)
    try:
        gw_config.brownout_max_tokens = 3
        async with aiohttp.ClientSession() as s:
            r = await s.post(
                f"{st.gw_url}/v1/completions",
                json={"prompt": PROMPT, "max_tokens": 64,
                      "temperature": 0},
            )
            assert r.status == 200, await r.text()
            assert (await r.json())["usage"]["completion_tokens"] == 3
    finally:
        await st.close()
