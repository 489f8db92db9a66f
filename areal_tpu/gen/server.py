"""Generation HTTP server.

TPU-native counterpart of the reference's patched-SGLang server +
``GenerationServer`` wrapper (``realhf/system/generation_server.py``): an
aiohttp app around :class:`GenerationEngine` exposing the same protocol
surface the rollout side depends on —

- ``POST /generate``: submit a request, await completion (or interruption).
- ``POST /generate_stream``: same request shape, but the response is an
  SSE stream of per-chunk token deltas (the engine's per-chunk harvest
  protocol made visible over HTTP — what the serving gateway's
  continuous-batching frontend consumes, docs/serving.md). A client
  disconnect mid-stream cancels the request and releases its slot.
- ``POST /update_weights_from_disk``: pause → harvest running requests as
  interrupted (clients re-submit, ≈ the SGLang ``InterruptAllReq`` patch) →
  reload params from an HF checkpoint dir → resume. Returns ``num_paused``.
- ``POST /pause_generation`` / ``POST /continue_generation``.
- ``GET /health``, ``GET /metrics_json`` (running/served counters, version).

The engine's jitted chunks execute in a thread-pool executor so the asyncio
loop stays responsive; one background task drives admission/decode
continuously (the reference's event loop lives inside SGLang's scheduler).
"""

import asyncio
import json
import logging
import os
import time
from typing import Dict, Optional

from aiohttp import web

from areal_tpu.base import constants, faults, hbm, tracing
from areal_tpu.base import metrics as metrics_mod
from areal_tpu.gen.engine import GenerationEngine, GenOutput, GenRequest

logger = logging.getLogger("areal_tpu.gen.server")


class RequestValidationError(ValueError):
    """Malformed /generate payload — answered 400, never a 500 from deep
    inside the engine (4xx does not feed the manager's circuit breaker)."""


def parse_generate_request(
    d: dict, vocab_size: int, max_capacity: int, max_new_cap: int = 1 << 30
) -> GenRequest:
    """Validate a /generate(-_stream) JSON body into a GenRequest.

    Every reachable malformation is rejected HERE with a message naming
    the offending field; the engine only ever sees well-formed requests."""
    if not isinstance(d, dict):
        raise RequestValidationError("body must be a JSON object")
    if "rid" not in d:
        raise RequestValidationError("missing required field 'rid'")
    ids = d.get("input_ids")
    if not isinstance(ids, (list, tuple)) or not ids:
        raise RequestValidationError(
            "'input_ids' must be a non-empty list of token ids"
        )
    try:
        ids = [int(t) for t in ids]
    except (TypeError, ValueError):
        raise RequestValidationError("'input_ids' must all be integers")
    bad = [t for t in ids if t < 0 or t >= vocab_size]
    if bad:
        raise RequestValidationError(
            f"input token {bad[0]} outside vocab [0, {vocab_size})"
        )
    sp = d.get("sampling_params", {})
    if not isinstance(sp, dict):
        raise RequestValidationError("'sampling_params' must be an object")
    try:
        max_new = int(sp.get("max_new_tokens", 256))
        min_new = int(sp.get("min_new_tokens", 0))
        temperature = float(sp.get("temperature", 1.0))
        top_p = float(sp.get("top_p", 1.0))
        top_k = int(sp.get("top_k", 1 << 30))
        greedy = bool(sp.get("greedy", False))
        stop_ids = [int(t) for t in sp.get("stop_token_ids", [])]
    except (TypeError, ValueError) as e:
        raise RequestValidationError(f"malformed sampling_params: {e}")
    if max_new < 1:
        raise RequestValidationError("max_new_tokens must be >= 1")
    if min_new < 0 or min_new > max_new:
        raise RequestValidationError(
            "min_new_tokens must be in [0, max_new_tokens]"
        )
    if temperature < 0.0:
        raise RequestValidationError("temperature must be >= 0")
    if not 0.0 < top_p <= 1.0:
        raise RequestValidationError("top_p must be in (0, 1]")
    if top_k < 1:
        raise RequestValidationError("top_k must be >= 1")
    # mirror engine.submit's admissibility check (max_new is clamped to
    # the engine's per-request cap before it counts against the slot)
    if len(ids) - 1 + min(max_new, max_new_cap) > max_capacity:
        raise RequestValidationError(
            f"prompt {len(ids)} + max_new_tokens {max_new} exceeds "
            f"per-slot capacity {max_capacity}"
        )
    return GenRequest(
        rid=str(d["rid"]),
        input_ids=ids,
        max_new_tokens=max_new,
        min_new_tokens=min_new,
        temperature=temperature,
        top_p=top_p,
        top_k=top_k,
        greedy=greedy,
        stop_token_ids=stop_ids,
    )


class GenerationHTTPServer:
    def __init__(
        self,
        engine: GenerationEngine,
        decode_steps: int = 16,
        metrics_dump_path: Optional[str] = None,
        overlap_load: bool = True,
        stream_interval_s: float = 0.0,
    ):
        # the engine's constructor started it; a stand-in engine did not
        tracing.listen_for_compiles()
        self.engine = engine
        self.decode_steps = decode_steps
        self.metrics_dump_path = metrics_dump_path
        # min seconds between streaming partial emissions: each emission
        # is ONE extra all-slot device pull riding the serve loop — 0
        # emits every chunk (lowest latency), a deployment co-resident
        # with RL traffic can set ~0.5 to bound the added host syncs
        # (cost per pull on an attached chip: not measured).
        # (Future: ride the chunk's existing flags-tuple sync instead.)
        self.stream_interval_s = stream_interval_s
        self._next_stream_emit = 0.0
        # stage new weights on device while decoding (2x transient param
        # residency); per-request overridable
        self.overlap_load = overlap_load
        self._futures: Dict[str, asyncio.Future] = {}
        # streaming subscriptions: rid -> event queue + tokens already sent
        # (the /generate_stream handler owns registration and cleanup)
        self._stream_subs: Dict[str, asyncio.Queue] = {}
        self._stream_sent: Dict[str, int] = {}
        self._served = 0
        self._gen_tokens = 0
        self._start = time.time()
        # phase accounting (where a serving round's wall time goes — the
        # observable the reference logs continuously,
        # realhf/system/gserver_manager.py:279-285): seconds inside engine
        # step calls, seconds swapping weights, interrupts issued
        self._t_step_busy = 0.0
        self._t_weight = 0.0
        self._t_weight_load = 0.0  # overlapped load time (NOT a stall)
        self._n_weight_updates = 0
        self._n_interrupted = 0
        self._hbm = hbm.HBMMonitor(tag="gen-server")
        self._lock = asyncio.Lock()
        self.app = web.Application()
        self._bind_routes(self.app)
        self.app.on_startup.append(self._on_startup)
        self.app.on_cleanup.append(self._on_cleanup)
        self._loop_task: Optional[asyncio.Task] = None

    def _bind_routes(self, app: web.Application) -> None:
        """The route table in one place: the wire-contract catalog test
        registers these on a bare Application (no engine construction)
        and diffs them against the statically parsed endpoint table."""
        app.router.add_post("/generate", self._generate)
        app.router.add_post("/generate_stream", self._generate_stream)
        app.router.add_post(
            "/update_weights_from_disk", self._update_weights
        )
        app.router.add_post("/pause_generation", self._pause)
        app.router.add_post("/continue_generation", self._continue)
        app.router.add_get("/health", self._health)
        app.router.add_get("/metrics_json", self._metrics)

    # ------------------------------------------------------------------ #
    # engine loop
    # ------------------------------------------------------------------ #

    async def _on_startup(self, app):
        self._loop_task = asyncio.get_event_loop().create_task(self._run())

    def _dump_metrics(self):
        """Phase accounting survives the process (the in-memory
        /metrics_json gauges die with it) — how a postmortem
        attributes where the serving side's wall time went."""
        try:
            with open(self.metrics_dump_path, "w") as f:
                json.dump(self._metrics_dict(), f)
        except OSError:
            logger.exception("could not dump gen-server metrics")

    async def _on_cleanup(self, app):
        if self._loop_task:
            self._loop_task.cancel()
        if self.metrics_dump_path:
            self._dump_metrics()

    def _resolve(self, outs):
        for o in outs:
            self._served += 1
            self._gen_tokens += len(o.output_ids)
            fut = self._futures.pop(o.rid, None)
            if fut is not None and not fut.done():
                fut.set_result(o)
            q = self._stream_subs.get(o.rid)
            if q is not None:
                sent = self._stream_sent.get(o.rid, 0)
                q.put_nowait(
                    {
                        "rid": o.rid,
                        "token_ids": o.output_ids[sent:],
                        "logprobs": o.output_logprobs[sent:],
                        "finish_reason": o.finish_reason,
                        "version": o.version,
                    }
                )

    async def _emit_stream_partials(self, loop):
        """Push the newest per-chunk token deltas to every live streaming
        subscriber: ONE device pull covers all of them (engine batching
        rule), run off the event loop because the pull can wait out an
        in-flight chunk."""
        rids = [r for r in self._stream_subs if r in self.engine._req_meta]
        if not rids:
            return
        partials = await loop.run_in_executor(
            None, self.engine.partial_outputs, rids
        )
        for rid, (toks, lps) in partials.items():
            q = self._stream_subs.get(rid)
            if q is None:
                continue
            sent = self._stream_sent.get(rid, 0)
            if len(toks) > sent:
                q.put_nowait(
                    {
                        "rid": rid,
                        "token_ids": toks[sent:],
                        "logprobs": lps[sent:],
                        "finish_reason": None,
                    }
                )
                self._stream_sent[rid] = len(toks)

    async def _run(self):
        loop = asyncio.get_event_loop()
        # HBM kill check rides a wall-clock period, NOT the chunk loop:
        # it is a watchdog, not a per-chunk gauge, and the live-array
        # fallback walks every buffer (≈ the reference's per-MFC check +
        # kill threshold, realhf/system/model_worker.py:1507-1512)
        hbm_period = constants.hbm_check_secs()
        next_hbm = time.time() + hbm_period
        # metrics dump rides the same loop: PERIODIC, not only at cleanup —
        # a SIGTERM'd worker (launcher straggler kill) must still leave its
        # phase accounting behind
        next_dump = time.time() + 10.0
        while True:
            if self.metrics_dump_path and time.time() >= next_dump:
                next_dump = time.time() + 10.0
                self._dump_metrics()
            if time.time() >= next_hbm:
                next_hbm = time.time() + hbm_period
                try:
                    # off the event loop: the live-array fallback walks
                    # every buffer (same reason _metrics offloads it)
                    await loop.run_in_executor(None, self._hbm.check)
                except hbm.HBMPressureError:
                    logger.critical(
                        "HBM past kill threshold; dying for launcher restart",
                        exc_info=True,
                    )
                    os._exit(1)
            if self.engine.paused or (
                not self.engine._pending
                and self.engine.n_running() == 0
                and not self.engine.has_inflight
            ):
                await asyncio.sleep(0.005)
                continue
            async with self._lock:
                t0 = time.monotonic()
                outs = await loop.run_in_executor(
                    None, self.engine.step, self.decode_steps
                )
                self._t_step_busy += time.monotonic() - t0
            self._resolve(outs)
            if self._stream_subs and time.monotonic() >= self._next_stream_emit:
                self._next_stream_emit = (
                    time.monotonic() + self.stream_interval_s
                )
                await self._emit_stream_partials(loop)

    # ------------------------------------------------------------------ #
    # handlers
    # ------------------------------------------------------------------ #

    async def _parse_request(self, request: web.Request):
        """Decode + validate one generate payload (raises
        RequestValidationError with a field-naming message); returns the
        GenRequest plus the raw body for transport-level fields the
        engine request does not carry (``deadline_s``)."""
        try:
            d = await request.json()
        except (ValueError, TypeError):
            raise RequestValidationError("body is not valid JSON")
        return parse_generate_request(
            d, self.engine.cfg.vocab_size, self.engine.S, self.engine.G
        ), d

    async def _generate(self, request: web.Request) -> web.Response:
        try:
            req, raw = await self._parse_request(request)
        except RequestValidationError as e:
            return web.json_response({"error": str(e)}, status=400)
        # join the caller's distributed trace (or root a fresh one) — the
        # optional 'trace' body field is the wire context every internal
        # client attaches (docs/observability.md "Distributed tracing")
        with tracing.activate(raw.get("trace")), tracing.span(
            "gen_server/generate", rid=req.rid
        ):
            fut = asyncio.get_event_loop().create_future()
            self._futures[req.rid] = fut
            try:
                # arealint: owns(gen.engine-slot, the engine loop harvests and releases the slot at finish; /generate serves RL rollout clients whose disconnects don't cancel by design — the sample is still wanted)
                self.engine.submit(req)
            except ValueError as e:
                self._futures.pop(req.rid, None)
                return web.json_response({"error": str(e)}, status=400)
            out: GenOutput = await fut
            # telemetry-plane activity counters (exported per worker; the
            # /metrics_json gauges below remain the pull-path view)
            metrics_mod.counters.add(metrics_mod.GEN_SERVED)
            metrics_mod.counters.add(
                metrics_mod.GEN_TOKENS, len(out.output_ids)
            )
            return web.json_response(
                {
                    "rid": out.rid,
                    "output_ids": out.output_ids,
                    "output_logprobs": out.output_logprobs,
                    "finish_reason": out.finish_reason,
                    "version": out.version,
                }
            )

    async def _generate_stream(self, request: web.Request) -> web.StreamResponse:
        """SSE variant of /generate: per-chunk token deltas as they are
        harvested, a final frame carrying ``finish_reason``, then
        ``data: [DONE]``. A client disconnect cancels the request and
        releases its engine slot immediately.

        An optional top-level ``deadline_s`` (remaining seconds of the
        caller's budget, stamped at request time) is enforced HERE as well
        as at the gateway: when it runs out mid-generation the server
        emits a final ``finish_reason: "deadline"`` frame and cancels the
        slot — the engine never burns chunks for an answer nobody is
        waiting for, even if the gateway's own cancel is slow to land."""
        try:
            req, raw = await self._parse_request(request)
        except RequestValidationError as e:
            return web.json_response({"error": str(e)}, status=400)
        deadline_t = None
        try:
            deadline_s = float(raw.get("deadline_s", 0.0) or 0.0)
        except (TypeError, ValueError):
            return web.json_response(
                {"error": "'deadline_s' must be a number"}, status=400
            )
        if deadline_s > 0:
            deadline_t = time.monotonic() + deadline_s
        # join the caller's distributed trace for the whole stream; the
        # riding RL qid (if any) lands in span attrs + disconnect logs so
        # the breaker's last_failure_reason joins against trace ids
        with tracing.activate(raw.get("trace")), tracing.span(
            "gen_server/generate_stream", rid=req.rid
        ) as span_attrs:
            loop = asyncio.get_event_loop()
            q: asyncio.Queue = asyncio.Queue()
            self._stream_subs[req.rid] = q
            self._stream_sent[req.rid] = 0
            try:
                # arealint: owns(gen.engine-slot, released by the engine's own harvest when 'finished', by the finally's _cancel_rid on disconnect/cancellation otherwise — the conditional is the protocol, not a gap)
                self.engine.submit(req)
            except ValueError as e:
                self._stream_subs.pop(req.rid, None)
                self._stream_sent.pop(req.rid, None)
                return web.json_response({"error": str(e)}, status=400)
            resp = web.StreamResponse(
                headers={
                    "Content-Type": "text/event-stream",
                    "Cache-Control": "no-cache",
                }
            )
            finished = False
            n_tokens = 0
            n_frames = 0
            try:
                await resp.prepare(request)
                try:
                    while True:
                        if (
                            deadline_t is not None
                            and time.monotonic() >= deadline_t
                        ):
                            # budget ran out mid-generation: final frame +
                            # slot cancel (finished stays False -> the
                            # finally below cancels the rid)
                            await resp.write(
                                b"data: " + json.dumps({
                                    "rid": req.rid, "token_ids": [],
                                    "logprobs": [],
                                    "finish_reason": "deadline",
                                }).encode() + b"\n\n"
                            )
                            await resp.write(b"data: [DONE]\n\n")
                            break
                        try:
                            ev = await asyncio.wait_for(q.get(), timeout=0.5)
                        except asyncio.TimeoutError:
                            # poll the transport so a silent disconnect
                            # releases the slot promptly, not at next write
                            tr = request.transport
                            if tr is None or tr.is_closing():
                                raise ConnectionResetError(
                                    "client went away"
                                )
                            continue
                        # serving-plane chaos hooks (tools/chaos.py
                        # --serve): a scripted backend death drops the
                        # stream without a final frame (FaultInjected IS a
                        # ConnectionError — the quiet-end path below
                        # cancels the slot exactly like a real mid-stream
                        # crash); a scripted wedge stalls the first chunk
                        # past the gateway's hedge delay
                        faults.maybe_fail(
                            "gw.backend_die_midstream", rid=req.rid
                        )
                        await faults.maybe_fail_async(
                            "gw.backend_wedge", rid=req.rid
                        )
                        await resp.write(
                            b"data: " + json.dumps(ev).encode() + b"\n\n"
                        )
                        n_frames += 1
                        n_tokens += len(ev.get("token_ids", ()))
                        if ev.get("finish_reason"):
                            finished = True
                            break
                    if finished:
                        await resp.write(b"data: [DONE]\n\n")
                except (ConnectionResetError, ConnectionError):
                    # client went away: not a server error — free the slot
                    # (in finally) and end the response quietly
                    logger.info(
                        "stream %s (qid=%s): client disconnected",
                        req.rid, tracing.current_qid(),
                    )
            finally:
                span_attrs["frames"] = n_frames
                span_attrs["tokens"] = n_tokens
                self._stream_subs.pop(req.rid, None)
                self._stream_sent.pop(req.rid, None)
                if not finished:
                    # disconnect / handler cancellation mid-generation:
                    # free the slot (engine lock can wait out a chunk ->
                    # executor)
                    await self._cancel_rid(loop, req.rid)
            metrics_mod.counters.add(metrics_mod.GEN_SERVED)
            metrics_mod.counters.add(metrics_mod.GEN_TOKENS, n_tokens)
            return resp

    async def _cancel_rid(self, loop, rid: str):
        """Cancel with a short retry: a rid can transiently be in neither
        the pending queue nor a slot while _admit_pending holds it in its
        local lookahead — cancel() returns False then, but _req_meta still
        lists the rid, so retry until the admission lands (or the request
        finished, which drops it from _req_meta)."""
        for _ in range(40):
            if await loop.run_in_executor(None, self.engine.cancel, rid):
                return
            if rid not in self.engine._req_meta:
                return  # already finished/harvested
            await asyncio.sleep(0.05)
        logger.warning("could not cancel %s (still mid-admission?)", rid)

    async def _update_weights(self, request: web.Request) -> web.Response:
        d = await request.json()
        path = d["model_path"]
        allow_interrupt = bool(d.get("allow_interrupt", True))
        overlap_load = bool(d.get("overlap_load", self.overlap_load))
        loop = asyncio.get_event_loop()
        params = None
        if overlap_load:
            # OVERLAPPED reload (r5, VERDICT r4 #3): read the checkpoint
            # and stage it on device while the engine keeps decoding — the
            # lock/pause window then contains only the pointer swap. Costs
            # a transient 2x param residency; the manager passes
            # overlap_load=false for models without that HBM headroom
            # (reference counterpart: gserver_manager.py:158-190 reload
            # scheduling around in-flight rollouts).
            t_load0 = time.monotonic()
            try:
                params = await loop.run_in_executor(
                    None, self._load_params, path
                )
            except Exception as e:  # noqa: BLE001 - reported to the manager
                logger.exception("weight load failed (engine untouched)")
                return web.json_response({
                    "success": False,
                    "message": f"weight update failed: {e!r}",
                    "num_paused_requests": 0,
                })
            self._t_weight_load += time.monotonic() - t_load0
        async with self._lock:
            # timer starts INSIDE the lock: waiting out an in-flight decode
            # chunk is step_busy time, not weight-swap time — double-booking
            # would make the dumped phases sum past uptime
            t_upd0 = time.monotonic()
            if allow_interrupt:
                interrupted = self.engine.pause()
                self._resolve(interrupted)
                num_paused = len(interrupted)
            else:
                # drain: stop admission (new requests queue in _pending),
                # decode the running slots to completion
                self.engine.accepting = False
                try:
                    while self.engine.n_running():
                        outs = await loop.run_in_executor(
                            None, self.engine.step, self.decode_steps
                        )
                        self._resolve(outs)
                finally:
                    self.engine.accepting = True
                self.engine.paused = True
                num_paused = 0
            try:
                if params is None:
                    params = await loop.run_in_executor(
                        None, self._load_params, path
                    )
                self.engine.update_params(params, version=d.get("version"))
                ok = True
                msg = f"loaded weights from {path}"
            except Exception as e:  # noqa: BLE001 - reported to the manager
                ok = False
                msg = f"weight update failed: {e!r}"
                logger.exception("weight update failed")
            self.engine.resume()
        self._t_weight += time.monotonic() - t_upd0
        self._n_weight_updates += 1
        self._n_interrupted += num_paused
        return web.json_response(
            {"success": ok, "message": msg, "num_paused_requests": num_paused}
        )

    def _load_params(self, path: str):
        from areal_tpu.models import hf as hf_conv

        _, host_params = hf_conv.load_hf_checkpoint(path)
        # cast + (when TP-sharded) mesh placement
        return self.engine.prepare_params(host_params)

    async def _pause(self, request: web.Request) -> web.Response:
        async with self._lock:
            interrupted = self.engine.pause()
            self._resolve(interrupted)
        return web.json_response({"num_paused_requests": len(interrupted)})

    async def _continue(self, request: web.Request) -> web.Response:
        self.engine.resume()
        return web.json_response({"success": True})

    async def _health(self, request: web.Request) -> web.Response:
        return web.json_response({"status": "ok"})

    def _metrics_dict(self) -> dict:
        return {
            "running": self.engine.n_running(),
            "pending": len(self.engine._pending),
            "served": self._served,
            "gen_tokens": self._gen_tokens,
            "version": self.engine.version,
            "max_slots": self.engine.B,
            # per-slot token capacity: the gateway's prompt-size bound
            "slot_capacity": self.engine.S,
            # weight-update pause flag: the gateway's hedge gate (a pause
            # stalls EVERY backend the same way — hedging it would double
            # the load for zero latency win)
            "paused": bool(self.engine.paused),
            # paged KV pool + prefix cache observability: bytes, dtype and
            # occupancy are the per-server HBM-headroom gauges the fleet
            # aggregator / apps/obs watch (docs/observability.md)
            # "pages_free" is the legacy alias of "n_pages_free" (the
            # fleet-gauge name) — keep both until scrapers migrate
            "pages_free": self.engine.pool.n_free,
            "pages_total": self.engine.n_pages,
            "n_pages_free": self.engine.pool.n_free,
            "kv_dtype": self.engine.kv_dtype,
            "kv_pool_bytes": self.engine.kv_pool_bytes(),
            # what one resident token takes of it (the model says: K and V
            # heads, or one padded latent row a layer)
            "cache_bytes_per_token": self.engine.cache_bytes_per_token(),
            # ... by layer kind (window and full layers in one stack hold a
            # token for different lengths of time), and the pages promised
            # to running slots and not yet taken (a window kind's later
            # pages; counted as held by ``kv_pool_occupancy``)
            "cache_bytes_per_token_by_kind": (
                self.engine.cache_bytes_per_token_by_kind()),
            "pages_reserved": self.engine.pool.reserved,
            "kv_pool_occupancy": round(self.engine.kv_pool_occupancy(), 4),
            # admission signal: excludes instantly-evictable cache-only
            # pages (the gateway gates dispatch on THIS, not the raw
            # occupancy — a cache-warm idle server is not "full")
            "kv_pool_demand_occupancy": round(
                self.engine.kv_pool_demand_occupancy(), 4
            ),
            "prefix_pages": len(self.engine.prefix),
            # phase accounting: where serving wall time went
            "uptime_s": round(time.time() - self._start, 3),
            "step_busy_s": round(self._t_step_busy, 3),
            "weight_update_s": round(self._t_weight, 3),
            "weight_load_overlapped_s": round(self._t_weight_load, 3),
            "n_weight_updates": self._n_weight_updates,
            "n_interrupted": self._n_interrupted,
            # fused sampling epilogue (docs/performance.md): streamed
            # LM-head sampling on the decode chunk
            "fused_sample": self.engine.fused,
            **{f"engine_{k}": v for k, v in self.engine.stats.items()},
        }

    async def _metrics(self, request: web.Request) -> web.Response:
        # HBM gauges off the event loop: the live-array fallback walks
        # every buffer — a scraper polling /metrics must not stall
        # /generate
        hbm_gauges = await asyncio.get_event_loop().run_in_executor(
            None, lambda: self._hbm.check(kill=False)
        )
        # gauges only on the pull path — a GET must never raise
        return web.json_response(
            # arealint: wire(/metrics_json, hbm gauge keys come from HBMMonitor.check at runtime)
            {**self._metrics_dict(), **hbm_gauges}
        )


async def serve(engine: GenerationEngine, host: str, port: int, **kw):
    """Start serving; returns the aiohttp AppRunner (caller owns shutdown)."""
    srv = GenerationHTTPServer(engine, **kw)
    runner = web.AppRunner(srv.app)
    await runner.setup()
    site = web.TCPSite(runner, host, port)
    await site.start()
    logger.info("generation server on %s:%d", host, port)
    return runner
