"""Async HTTP client for the generation fleet.

Counterpart of the reference's ``SGLangAPIClient``
(``realhf/impl/model/backend/sglang.py:62``): generate (buffered and
chunk-granular streaming, ``generate_stream``) + weight-update calls
with the same retry/timeout posture, hardened for preemptible fleets:

- capped exponential backoff with jitter on idempotent calls (generate and
  weight updates retry on *connection* errors only — a timeout proves the
  client gave up, not that the peer never saw the request, and the fan-out
  path must not multiply a black-holing server's timeout budget),
- per-call timeouts distinct from the session total (a health probe must
  answer in seconds even when the session budget covers minutes-long
  generates),
- named fault-injection points (``gen.http``, ``gen.weight_update``) so
  tests script failures deterministically (``areal_tpu/base/faults.py``).

Retries are observable via ``metrics.counters``: ``ft/client_retries``.
"""

import asyncio
import dataclasses
import json
import random
import time
from typing import Dict, List, Optional

import aiohttp

from areal_tpu.base import faults, tracing
from areal_tpu.base import metrics as metrics_mod


class DeadlineExceeded(asyncio.TimeoutError):
    """The request's overall deadline expired before the stream opened.

    Typed (instead of a generic timeout) so callers can tell "the client
    gave up per the caller's own budget" apart from "the peer black-holed
    the session total" — the former must NOT be retried anywhere."""

# the request never completed: safe to retry even non-idempotent calls
CONNECTION_ERRORS = (
    aiohttp.ClientConnectionError,  # refused / reset / disconnected
    ConnectionError,                # includes faults.FaultInjected
    asyncio.TimeoutError,
)
# 5xx the fleet emits while pausing/restarting — transient by contract
RETRYABLE_STATUS = (502, 503, 504)


@dataclasses.dataclass
class RetryPolicy:
    """Capped exponential backoff with full jitter."""

    max_attempts: int = 3
    backoff_base_s: float = 0.05
    backoff_cap_s: float = 2.0
    jitter: float = 0.5  # each delay is scaled by U[1-jitter, 1]

    def delay(self, attempt: int, rng: random.Random) -> float:
        d = min(self.backoff_cap_s, self.backoff_base_s * (2 ** attempt))
        return d * (1.0 - self.jitter * rng.random())


@dataclasses.dataclass
class GenReqMeta:
    """≈ ``model_api.GenReqMeta:46`` — what the router needs to pick a server."""

    qid: str
    prompt_len: int
    group_size: int
    new_token_budget: int
    predicted_new_tokens: Optional[int] = None


@dataclasses.dataclass
class APIGenerateResult:
    rid: str
    output_ids: List[int]
    output_logprobs: List[float]
    finish_reason: str
    version: int


class GenAPIClient:
    def __init__(
        self,
        timeout: float = 300.0,
        request_timeout: Optional[float] = None,
        retry: Optional[RetryPolicy] = None,
        seed: Optional[int] = None,
    ):
        """``timeout`` bounds the whole session (the longest generate);
        ``request_timeout`` bounds one control-plane call (health/metrics) —
        defaults to min(10s, timeout)."""
        self._timeout = aiohttp.ClientTimeout(total=timeout)
        self._request_timeout = aiohttp.ClientTimeout(
            total=min(10.0, timeout) if request_timeout is None
            else request_timeout
        )
        self.retry = retry or RetryPolicy()
        self._rng = random.Random(seed)
        self._session: Optional[aiohttp.ClientSession] = None

    async def __aenter__(self):
        self._session = aiohttp.ClientSession(timeout=self._timeout)
        return self

    async def __aexit__(self, *exc):
        await self._session.close()

    # ------------------------------------------------------------------ #
    # retrying request core
    # ------------------------------------------------------------------ #

    async def _request_json(
        self,
        method: str,
        server_url: str,
        endpoint: str,
        op: str,
        json_body: Optional[Dict] = None,
        timeout: Optional[aiohttp.ClientTimeout] = None,
        retry_connection_only: bool = False,
    ) -> Dict:
        """One logical call = up to ``retry.max_attempts`` HTTP attempts.

        ``retry_connection_only`` restricts retries to errors where the
        request provably never completed (generate: re-sending a request the
        server may be running would double-bill its rid)."""
        attempt = 0
        # aiohttp treats an explicit timeout=None as "no timeout at all"
        # (not "session default"), so the kwarg is only passed when set —
        # otherwise the session total (the long generate budget) applies
        req_kw: Dict = {"json": json_body}
        if timeout is not None:
            req_kw["timeout"] = timeout
        while True:
            try:
                await faults.maybe_fail_async(
                    "gen.http", url=server_url, op=op
                )
                async with self._session.request(
                    method, f"{server_url}{endpoint}", **req_kw
                ) as resp:
                    if resp.status in RETRYABLE_STATUS:
                        resp.release()
                        raise aiohttp.ClientResponseError(
                            resp.request_info, (), status=resp.status,
                            message="transient server status",
                        )
                    resp.raise_for_status()
                    return await resp.json()
            except Exception as e:
                if retry_connection_only:
                    # a timeout proves the client gave up, NOT that the
                    # request never reached the server — resending a
                    # possibly-still-running generate would double-bill it
                    retryable = isinstance(
                        e, CONNECTION_ERRORS
                    ) and not isinstance(e, asyncio.TimeoutError)
                else:
                    retryable = isinstance(e, CONNECTION_ERRORS) or (
                        isinstance(e, aiohttp.ClientResponseError)
                        and e.status in RETRYABLE_STATUS
                    )
                attempt += 1
                if not retryable or attempt >= self.retry.max_attempts:
                    raise
                metrics_mod.counters.add(metrics_mod.FT_CLIENT_RETRIES)
                await asyncio.sleep(self.retry.delay(attempt - 1, self._rng))

    # ------------------------------------------------------------------ #
    # API calls
    # ------------------------------------------------------------------ #

    async def generate(
        self,
        server_url: str,
        rid: str,
        input_ids: List[int],
        sampling_params: Dict,
    ) -> APIGenerateResult:
        with tracing.span("gen_client/generate", rid=rid):
            body = {
                "rid": rid,
                "input_ids": input_ids,
                "sampling_params": sampling_params,
            }
            trace = tracing.wire_context()
            if trace is not None:
                # the hop's trace context (docs/observability.md
                # "Distributed tracing") — the server activates it so its
                # spans join this one as children
                body["trace"] = trace
            d = await self._request_json(
                "POST",
                server_url,
                "/generate",
                op="generate",
                json_body=body,
                retry_connection_only=True,
            )
        return APIGenerateResult(
            rid=d["rid"],
            output_ids=d["output_ids"],
            output_logprobs=d["output_logprobs"],
            finish_reason=d["finish_reason"],
            version=d["version"],
        )

    async def generate_stream(
        self,
        server_url: str,
        rid: str,
        input_ids: List[int],
        sampling_params: Dict,
        deadline_s: Optional[float] = None,
    ):
        """Chunk-granular async iterator over ``/generate_stream``: yields
        one dict per SSE frame (``token_ids``/``logprobs`` deltas; the
        final frame carries ``finish_reason`` + ``version``).

        The retry/backoff policy applies ONLY to the pre-first-chunk
        connect (connection refused fails in milliseconds and provably
        never reached the engine); once the response is open, a drop
        mid-stream surfaces to the caller — the server may have generated
        and the slot-cancel path owns cleanup, so re-sending here would
        double-bill the rid (same posture as ``generate``).

        ``deadline_s`` is the request's REMAINING deadline budget in
        seconds at call time: the connect-retry backoff never sleeps past
        it (raising :class:`DeadlineExceeded` instead of burning the full
        attempt budget on a request the caller will discard), and it is
        forwarded in the body so the gen server sheds the slot when the
        budget runs out mid-generation."""
        body = {
            "rid": rid,
            "input_ids": input_ids,
            "sampling_params": sampling_params,
        }
        trace = tracing.wire_context()
        if trace is not None:
            body["trace"] = trace
        t_deadline = None
        if deadline_s is not None and deadline_s > 0:
            body["deadline_s"] = float(deadline_s)
            t_deadline = time.monotonic() + deadline_s
        attempt = 0
        while True:
            if t_deadline is not None and time.monotonic() >= t_deadline:
                raise DeadlineExceeded(
                    f"deadline expired before the stream for {rid} opened"
                )
            try:
                await faults.maybe_fail_async(
                    "gen.http", url=server_url, op="generate_stream"
                )
                resp = await self._session.post(
                    f"{server_url}/generate_stream", json=body
                )
                break
            except Exception as e:
                retryable = isinstance(
                    e, CONNECTION_ERRORS
                ) and not isinstance(e, asyncio.TimeoutError)
                attempt += 1
                if not retryable or attempt >= self.retry.max_attempts:
                    raise
                delay = self.retry.delay(attempt - 1, self._rng)
                if (
                    t_deadline is not None
                    and time.monotonic() + delay >= t_deadline
                ):
                    # backing off past the deadline would hand the caller
                    # a stream it must immediately discard
                    raise DeadlineExceeded(
                        f"deadline expired during connect backoff for {rid}"
                    ) from e
                metrics_mod.counters.add(metrics_mod.FT_CLIENT_RETRIES)
                await asyncio.sleep(delay)
        try:
            resp.raise_for_status()
            async for raw in resp.content:
                line = raw.strip()
                if not line.startswith(b"data:"):
                    continue
                payload = line[len(b"data:"):].strip()
                if payload == b"[DONE]":
                    break
                yield json.loads(payload)
        finally:
            resp.release()

    async def update_weights_from_disk(
        self,
        server_url: str,
        model_path: str,
        version: Optional[int] = None,
        allow_interrupt: bool = True,
    ) -> Dict:
        await faults.maybe_fail_async("gen.weight_update", url=server_url)
        # connection-only retries: connection-refused fails in milliseconds
        # and is worth retrying, but a black-holing server must burn the
        # timeout budget at most ONCE — the manager's fan-out awaits the
        # slowest server, so timeout x max_attempts would multiply the
        # fleet-wide flush wedge (eviction + the probe loop own stragglers)
        return await self._request_json(
            "POST",
            server_url,
            "/update_weights_from_disk",
            op="update_weights",
            json_body={
                "model_path": model_path,
                "version": version,
                "allow_interrupt": allow_interrupt,
            },
            retry_connection_only=True,
        )

    async def post_json(
        self, server_url: str, endpoint: str, json_body: Dict,
        op: str = "control",
    ) -> Dict:
        """Generic idempotent control-plane POST (manager /add_server,
        /remove_server, ...): short per-call timeout, full retry policy —
        the public surface for endpoints without a dedicated wrapper."""
        return await self._request_json(
            "POST", server_url, endpoint, op=op, json_body=json_body,
            timeout=self._request_timeout,
        )

    async def metrics(self, server_url: str) -> Dict:
        return await self._request_json(
            "GET", server_url, "/metrics_json", op="metrics",
            timeout=self._request_timeout,
        )

    async def health(self, server_url: str) -> bool:
        """Single non-retried probe with the short per-call timeout — the
        breaker's half-open logic supplies the retry cadence."""
        try:
            await faults.maybe_fail_async(
                "gen.http", url=server_url, op="health"
            )
            async with self._session.get(
                f"{server_url}/health", timeout=self._request_timeout
            ) as resp:
                return resp.status == 200
        except (aiohttp.ClientError, ConnectionError, asyncio.TimeoutError):
            return False
