"""SLO-aware asyncio HTTP front door over :class:`GoService`.

The production serving tier of the reproduction (ROADMAP item 2): a thin
network surface over the ticketed queue -> poll protocol, so the PR 5
streaming pipeline's host/device overlap becomes *user-visible* latency
instead of an internal ``host_blocked_s`` counter.  Pure stdlib asyncio
(no framework dependency): requests are parsed off the stream, JSON in /
JSON out, connections keep-alive.

Endpoints::

    POST /v1/submit     {board, to_play?, komi?, sims?, c_uct?,
                         virtual_loss?, key?, deadline_ms?} -> {ticket}
    GET  /v1/result/T   {done: false} | the move payload | 410 if shed
    POST /v1/best_move  submit + await in one call (same body)
    GET  /metrics       ServingMetrics snapshot + outstanding depth
    GET  /healthz       {ok: true}

Load shedding is an HTTP status, never a hang: 503 for over-capacity
admission, 504 for a deadline shed (unmeetable at admission, expired in
queue, or still unanswered at its deadline).  Requests already on the
device always complete — a late answer is served with
``deadline_missed: true`` and counted, which is the honest half of the
SLO contract (the device program cannot be preempted mid-superstep).

Threading model: **all** GoService access runs on one single-thread
executor (``_call``) — submissions, polls, metrics reads — so the
service needs no internal locking and the asyncio event loop never
blocks on a device superstep.  One background pump task drives
``GoService.poll()`` whenever work is outstanding and resolves the
per-ticket futures that blocking ``best_move`` callers await.
"""
from __future__ import annotations

import asyncio
import functools
import json
from concurrent.futures import ThreadPoolExecutor
from typing import Dict, Optional, Tuple

from repro.serving.go_service import (DeadlineExceededError, GoService,
                                      MoveResult, OverCapacityError)

_JSON = {"Content-Type": "application/json"}


def _move_payload(res: MoveResult) -> dict:
    """JSON shape of one answered query (floats stay bit-exact: every
    float32 is exactly representable as a JSON double)."""
    return {
        "done": True,
        "ticket": res.ticket,
        "action": int(res.action),
        "coord": list(res.coord) if res.coord is not None else None,
        "is_pass": bool(res.is_pass),
        "root_visits": [float(v) for v in res.root_visits],
        "sims_granted": int(res.sims_granted),
        "downgraded": bool(res.downgraded),
        "latency_ms": res.latency_s * 1e3,
    }


class GoMoveServer:
    """Asyncio HTTP server wrapping one :class:`GoService`.

    ``poll_idle_s`` is the pump task's sleep when no work is
    outstanding; with work queued the pump spins as fast as the device
    answers (each ``poll()`` blocks in the executor on a superstep, not
    in the event loop).  ``await start()`` binds (port 0 picks a free
    one — the tests and the load bench use that), ``await stop()``
    drains the pump and closes the listener.
    """

    def __init__(self, service: GoService, poll_idle_s: float = 0.002,
                 best_move_timeout_s: float = 300.0):
        self.service = service
        self.poll_idle_s = poll_idle_s
        self.best_move_timeout_s = best_move_timeout_s
        self.host: Optional[str] = None
        self.port: Optional[int] = None
        self._exec = ThreadPoolExecutor(
            max_workers=1, thread_name_prefix="goservice")
        self._futures: Dict[int, asyncio.Future] = {}
        self._server: Optional[asyncio.AbstractServer] = None
        self._pump_task: Optional[asyncio.Task] = None

    # ------------------------------------------------------------- lifecycle

    async def start(self, host: str = "127.0.0.1", port: int = 0) -> int:
        """Bind and start serving; returns the bound port."""
        self._server = await asyncio.start_server(self._handle, host, port)
        self.host = host
        self.port = self._server.sockets[0].getsockname()[1]
        self._pump_task = asyncio.ensure_future(self._pump_loop())
        return self.port

    async def stop(self) -> None:
        """Stop the listener and the pump task; fail pending waiters."""
        if self._pump_task is not None:
            self._pump_task.cancel()
            try:
                await self._pump_task
            except asyncio.CancelledError:
                pass
            self._pump_task = None
        if self._server is not None:
            self._server.close()
            await self._server.wait_closed()
            self._server = None
        for fut in self._futures.values():
            if not fut.done():
                fut.set_exception(ConnectionError("server stopped"))
        self._futures.clear()
        self._exec.shutdown(wait=False)

    async def _call(self, fn, *args, **kw):
        """Run one GoService operation on the single service thread."""
        loop = asyncio.get_event_loop()
        if kw:
            fn = functools.partial(fn, **kw)
        return await loop.run_in_executor(self._exec, fn, *args)

    # ------------------------------------------------------------- pump loop

    def _pump_once(self):
        """One service-thread pump: poll, fetch results, drain sheds."""
        svc = self.service
        done = []
        if svc.outstanding > 0:
            for ticket in svc.poll():
                done.append((ticket, svc.result(ticket, wait=False)))
        shed = []
        for ticket, reason in svc.pop_shed().items():
            try:                 # consume the shed ticket's bookkeeping
                svc.result(ticket, wait=False)
            except DeadlineExceededError:
                pass
            shed.append((ticket, reason))
        return done, shed

    async def _pump_loop(self) -> None:
        """Drive GoService.poll() and resolve per-ticket futures."""
        while True:
            done, shed = await self._call(self._pump_once)
            for ticket, res in done:
                fut = self._futures.get(ticket)
                if fut is not None and not fut.done():
                    fut.set_result(res)
            for ticket, reason in shed:
                fut = self._futures.get(ticket)
                if fut is not None and not fut.done():
                    fut.set_exception(DeadlineExceededError(
                        f"ticket {ticket} shed ({reason})"))
            if not done and not shed:
                await asyncio.sleep(self.poll_idle_s)

    # --------------------------------------------------------------- routing

    def _submit(self, body: dict) -> int:
        """Service-thread submission; raises the shed exceptions."""
        key = body.get("key")
        return self.service.submit(
            body["board"],
            to_play=int(body.get("to_play", 1)),
            komi=body.get("komi"),
            sims=int(body.get("sims", 0)),
            key=key if key is None else list(key),
            c_uct=body.get("c_uct"),
            virtual_loss=body.get("virtual_loss"),
            deadline_ms=body.get("deadline_ms"),
        )

    async def _route(self, method: str, path: str,
                     body: Optional[dict]) -> Tuple[int, dict]:
        """Dispatch one parsed request to its handler."""
        if method == "GET" and path == "/healthz":
            return 200, {"ok": True}
        if method == "GET" and path == "/metrics":
            return 200, await self._call(self._metrics_payload)
        if method == "POST" and path in ("/v1/submit", "/v1/best_move"):
            if body is None or "board" not in body:
                return 400, {"error": "bad_request",
                             "detail": "JSON body with 'board' required"}
            loop = asyncio.get_event_loop()
            try:
                ticket = await self._call(self._submit, body)
            except OverCapacityError as e:
                return 503, {"error": "over_capacity", "detail": str(e)}
            except DeadlineExceededError as e:
                return 504, {"error": "deadline_shed", "detail": str(e)}
            except (KeyError, TypeError, ValueError) as e:
                return 400, {"error": "bad_request", "detail": str(e)}
            fut = loop.create_future()
            self._futures[ticket] = fut
            if path == "/v1/submit":
                return 200, {"ticket": ticket}
            try:
                # late answers are served (flagged + counted as misses),
                # so the wait bound is the server's, not the deadline's
                timeout = self.best_move_timeout_s
                res = await asyncio.wait_for(fut, timeout)
            except DeadlineExceededError as e:
                return 504, {"error": "deadline_shed", "detail": str(e)}
            except asyncio.TimeoutError:
                return 504, {"error": "timeout",
                             "detail": f"no answer in {timeout:.1f}s"}
            finally:
                self._futures.pop(ticket, None)
            return 200, self._finish(res, body)
        if method == "GET" and path.startswith("/v1/result/"):
            try:
                ticket = int(path.rsplit("/", 1)[1])
            except ValueError:
                return 400, {"error": "bad_request",
                             "detail": "ticket must be an integer"}
            fut = self._futures.get(ticket)
            if fut is None:
                return 404, {"error": "unknown_ticket", "ticket": ticket}
            if not fut.done():
                return 200, {"done": False, "ticket": ticket}
            self._futures.pop(ticket, None)
            try:
                res = fut.result()
            except DeadlineExceededError as e:
                return 410, {"error": "deadline_shed", "detail": str(e)}
            return 200, _move_payload(res)
        return 404, {"error": "not_found", "path": path}

    def _finish(self, res: MoveResult, body: dict) -> dict:
        """Annotate a served answer with its deadline verdict."""
        payload = _move_payload(res)
        deadline_ms = body.get("deadline_ms")
        payload["deadline_missed"] = bool(
            deadline_ms is not None and payload["latency_ms"] > deadline_ms)
        return payload

    def _metrics_payload(self) -> dict:
        """Service-thread /metrics snapshot."""
        svc = self.service
        return {
            "metrics": svc.metrics.snapshot(),
            "outstanding": svc.outstanding,
            "buckets": sorted(
                svc._sched.buckets if svc.unified else svc._buckets),
            "admission_limit": svc.admission_limit,
            "host_syncs": svc.host_syncs,
            "host_blocked_s": svc.host_blocked_s,
            "host_busy_s": svc.host_busy_s,
            # per-bucket occupancy / queue depth / in-flight supersteps
            "scheduler": svc.scheduler_stats(),
            "shard_occupancy": [float(x) for x in svc.shard_occupancy()],
        }

    # ------------------------------------------------------------------ http

    async def _handle(self, reader: asyncio.StreamReader,
                      writer: asyncio.StreamWriter) -> None:
        """Serve one keep-alive connection: parse, route, respond."""
        try:
            while True:
                line = await reader.readline()
                if not line or line in (b"\r\n", b"\n"):
                    break
                try:
                    method, path, _ = line.decode("latin1").split()
                except ValueError:
                    break
                headers = {}
                while True:
                    h = await reader.readline()
                    if h in (b"\r\n", b"\n", b""):
                        break
                    k, _, v = h.decode("latin1").partition(":")
                    headers[k.strip().lower()] = v.strip()
                length = int(headers.get("content-length", 0))
                raw = await reader.readexactly(length) if length else b""
                body = None
                if raw:
                    try:
                        body = json.loads(raw)
                    except json.JSONDecodeError:
                        body = None
                try:
                    status, payload = await self._route(method, path, body)
                except Exception as e:   # never drop a connection silently
                    status, payload = 500, {"error": "internal",
                                            "detail": repr(e)}
                data = json.dumps(payload).encode()
                writer.write(
                    b"HTTP/1.1 %d OK\r\n"
                    b"Content-Type: application/json\r\n"
                    b"Content-Length: %d\r\n"
                    b"Connection: keep-alive\r\n\r\n"
                    % (status, len(data)))
                writer.write(data)
                await writer.drain()
                if headers.get("connection", "").lower() == "close":
                    break
        except (asyncio.IncompleteReadError, ConnectionResetError):
            pass
        finally:
            writer.close()
            try:
                await writer.wait_closed()
            except (ConnectionResetError, BrokenPipeError):
                pass


async def http_json(host: str, port: int, method: str, path: str,
                    payload: Optional[dict] = None,
                    timeout_s: float = 120.0) -> Tuple[int, dict]:
    """Minimal one-shot JSON-over-HTTP client (stdlib asyncio streams).

    The test suite and benchmarks/bench_load.py drive the front door
    with this instead of pulling in an HTTP client dependency.  Returns
    ``(status, decoded_body)``.
    """
    reader, writer = await asyncio.open_connection(host, port)
    try:
        body = b"" if payload is None else json.dumps(payload).encode()
        head = (f"{method} {path} HTTP/1.1\r\nHost: {host}\r\n"
                f"Content-Type: application/json\r\n"
                f"Content-Length: {len(body)}\r\nConnection: close\r\n\r\n")
        writer.write(head.encode() + body)
        await writer.drain()

        async def read_all():
            status_line = await reader.readline()
            status = int(status_line.split()[1])
            length = None
            while True:
                h = await reader.readline()
                if h in (b"\r\n", b"\n", b""):
                    break
                k, _, v = h.decode("latin1").partition(":")
                if k.strip().lower() == "content-length":
                    length = int(v)
            raw = (await reader.readexactly(length) if length is not None
                   else await reader.read())
            return status, json.loads(raw) if raw else {}

        return await asyncio.wait_for(read_all(), timeout_s)
    finally:
        writer.close()
        try:
            await writer.wait_closed()
        except (ConnectionResetError, BrokenPipeError):
            pass
