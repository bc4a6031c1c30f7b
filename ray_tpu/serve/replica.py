"""Replica actor: wraps the user callable, serves requests, reports health +
queue depth, supports streaming and graceful drain.

Reference: ``python/ray/serve/_private/replica.py`` (RayServeReplica).  Runs as
an async actor with ``max_concurrency = max_concurrent_queries`` so requests
interleave on the replica's event loop; ``num_ongoing`` is both the router's
power-of-two-choices signal and the autoscaler's input.
"""

from __future__ import annotations

import asyncio
import inspect
import time
from typing import Any, Dict, Optional

import cloudpickle

from ray_tpu.core.runtime_context import _task_context

from . import observability as obs


class Request:
    """Lightweight HTTP request container handed to deployments that take one
    (the reference hands a starlette Request; same role)."""

    __slots__ = ("method", "path", "query", "headers", "body")

    def __init__(self, method: str = "POST", path: str = "/", query=None,
                 headers=None, body: bytes = b""):
        self.method = method
        self.path = path
        self.query = dict(query or {})
        self.headers = dict(headers or {})
        self.body = body

    def json(self):
        import json
        return json.loads(self.body or b"null")

    def text(self) -> str:
        return (self.body or b"").decode()


class ReplicaActor:
    """The actor class every replica runs (created by the controller)."""

    def __init__(self, deployment_name: str, replica_id: str, app_blob: bytes,
                 user_config: Any = None):
        self.deployment_name = deployment_name
        self.replica_id = replica_id
        func_or_class, init_args, init_kwargs = cloudpickle.loads(app_blob)
        if inspect.isclass(func_or_class):
            self.callable = func_or_class(*init_args, **init_kwargs)
            self._entry = None  # resolve per request (method or __call__)
        else:
            self.callable = None
            self._fn = func_or_class
        #: a user request's way in and out, by leg; a deployment that
        #: declares ``request_account`` gets it to show (LLMServer.stats())
        self.account = obs.RequestAccount()
        if hasattr(self.callable, "request_account"):
            self.callable.request_account = self.account
        self.num_ongoing = 0
        self.num_processed = 0
        self._draining = False
        self.started_at = time.time()
        self._streams: Dict[str, list] = {}
        self._stream_done: Dict[str, bool] = {}
        #: a user request's stream -> its stamps (obs.RequestTrack)
        self._stream_tracks: Dict[str, obs.RequestTrack] = {}
        #: streams the CLIENT abandoned (stream timeout) -> cancel ts:
        #: the generator stops buffering and the finally path must not
        #: resurrect the done-flag entry — an unclaimed buffer would
        #: block drain() forever and leak per-stream memory.  A dict
        #: (not a set) so tombstones that are never consumed (cancel
        #: raced a completed-and-popped stream; ids are fresh uuids) age
        #: out instead of accumulating for the replica's lifetime.
        self._cancelled_streams: Dict[str, float] = {}
        if user_config is not None:
            self._apply_user_config(user_config)

    # ------------------------------------------------------- observability

    def _arrived(self, method: Optional[str], sent_at: Optional[float],
                 buffered: bool = False) -> Optional[obs.RequestTrack]:
        """The first line of the three serving methods: a user request
        (``method is None``) gets its stamps: the caller's ``sent_at``
        where it sent one, the call's arrival in this process from the
        task context (``core_worker._task_ctx``), and now.
        ``obs.RequestTrack`` books the way in from them.  ``stats``,
        ``next_chunks`` and other named methods are no requests."""
        if method is not None:
            return None
        task = _task_context.get()
        return obs.RequestTrack(
            self.account, sent_at,
            task.get("received_at") if task else None, buffered)

    def _obs_begin(self, track: Optional[obs.RequestTrack]):
        """Per-request instrumentation entry: install the event-loop stall
        monitor once (this runs ON the actor loop — __init__ does not),
        publish queue depth, and tag downstream instrumentation
        (@serve.batch, the LLM engine) with this deployment's config
        name and the request's stamps.  Returns (t0, ctx tokens) for
        _obs_end."""
        obs.ensure_loop_monitor(
            self, f"serve_replica:{self.deployment_name}")
        obs.set_replica_queue_depth(self.deployment_name, self.num_ongoing)
        return (time.monotonic(),
                obs.set_current_deployment(self.deployment_name),
                obs.set_current_request(track))

    def _obs_end(self, begin, first_token_at: Optional[float] = None,
                 ok: bool = True, window: bool = True):
        """Request done: one TTFT sample into the histogram + rolling SLO
        window (streaming requests pass their first-chunk time; unary
        requests' TTFT is their full latency — the first response byte).
        Failed requests don't feed anything (an instant exception is not a
        fast first token — it would drag the SLO percentiles DOWN exactly
        when the deployment is misbehaving), and named-method calls
        (``window=False``: h.stats.remote() and other introspection/
        control routes) skip the WINDOW so fast non-inference polls can't
        mask real serving degradation — they still land in the TTFT
        histogram under the same deployment tag."""
        t0, token, request_token = begin
        obs.set_replica_queue_depth(self.deployment_name, self.num_ongoing)
        if ok:
            obs.observe_ttft(self.deployment_name,
                             (first_token_at if first_token_at is not None
                              else time.monotonic()) - t0,
                             window=window)
        # last: the ctx resets are the one step that can be running inside
        # asyncgen finalization (foreign context) — nothing may depend on it
        obs.reset_current_deployment(token)
        obs.reset_current_request(request_token)

    # ------------------------------------------------------------- serving

    def _resolve(self, method: Optional[str]):
        if self.callable is None:
            return self._fn
        target = self.callable
        if method:
            return getattr(target, method)
        if callable(target):
            return target.__call__
        raise AttributeError(f"{type(target)} is not callable; specify method")

    async def handle_request(self, args: tuple, kwargs: dict,
                             method: Optional[str] = None,
                             sent_at: Optional[float] = None) -> Any:
        """``sent_at`` (here and on the two streaming methods): the
        caller's wall clock before it chose a replica, where program code
        makes the call (the router, the proxies)."""
        track = self._arrived(method, sent_at)
        if self._draining:
            raise RuntimeError(f"replica {self.replica_id} is draining")
        self.num_ongoing += 1
        begin = self._obs_begin(track)
        ok = False
        try:
            if args and isinstance(args[0], Request):
                from .multiplex import _set_current_model_id
                _set_current_model_id(args[0])
            fn = self._resolve(method)
            out = fn(*args, **kwargs)
            if inspect.iscoroutine(out):
                out = await out
            if inspect.isgenerator(out) or inspect.isasyncgen(out):
                raise TypeError(
                    "streaming responses go through handle_request_streaming")
            ok = True
            return out
        finally:
            self.num_ongoing -= 1
            self.num_processed += 1
            self._obs_end(begin, ok=ok, window=method is None)

    async def handle_request_streaming(self, stream_id: str, args: tuple,
                                       kwargs: dict,
                                       method: Optional[str] = None,
                                       sent_at: Optional[float] = None
                                       ) -> None:
        """Run a (async) generator endpoint, buffering chunks for the caller
        to drain via next_chunks() — streaming over the actor RPC plane.
        Every chunk's append is stamped on the request's track; the poll
        that takes it books what it waited."""
        track = self._arrived(method, sent_at, buffered=True)
        if stream_id in self._cancelled_streams:
            # cancel raced ahead of a queued start: never register (and
            # consume the tombstone BEFORE the draining check — either
            # refusal must not leave it behind)
            self._cancelled_streams.pop(stream_id, None)
            raise RuntimeError(f"stream {stream_id} cancelled before start")
        if self._draining:
            raise RuntimeError(f"replica {self.replica_id} is draining")
        self.num_ongoing += 1
        self._streams[stream_id] = []
        self._stream_done[stream_id] = False
        if track is not None:
            self._stream_tracks[stream_id] = track
        begin = self._obs_begin(track)
        first_at: Optional[float] = None
        ok = False
        try:
            fn = self._resolve(method)
            out = fn(*args, **kwargs)

            def put(chunk) -> bool:
                # False once the client cancelled (stream timeout): stop
                # generating instead of appending into a popped buffer
                b = self._streams.get(stream_id)
                if b is None:
                    return False
                b.append(chunk)
                if track is not None:
                    track.append()
                return True

            if inspect.isasyncgen(out):
                async for chunk in out:
                    if first_at is None:
                        first_at = time.monotonic()
                    if not put(chunk):
                        break
            elif inspect.isgenerator(out):
                for chunk in out:
                    if first_at is None:
                        first_at = time.monotonic()
                    if not put(chunk):
                        break
                    await asyncio.sleep(0)  # let pollers interleave
            else:
                if inspect.iscoroutine(out):
                    out = await out
                put(out)
            ok = True
        finally:
            if track is not None and track.ended_at is None:
                # a deployment that does not say when its request was over
                # (LLMServer does: the engine's retire): the generator's end
                track.ended_at = time.monotonic()
            if stream_id in self._cancelled_streams:
                # abandoned: every trace of the stream is already gone —
                # resurrecting the done flag would leak an entry forever
                self._cancelled_streams.pop(stream_id, None)
                self._forget(stream_id)
            else:
                self._stream_done[stream_id] = True
            self.num_ongoing -= 1
            self.num_processed += 1
            self._obs_end(begin, first_token_at=first_at, ok=ok,
                          window=method is None)

    async def handle_request_gen(self, args: tuple, kwargs: dict,
                                 method: Optional[str] = None,
                                 sent_at: Optional[float] = None):
        """Streaming endpoint as a native streaming-generator actor method
        (called with ``num_returns="streaming"``): each chunk ships to the
        caller the moment it is yielded — no next_chunks long-poll round
        trips (that path remains for deployment handles that want the
        buffered protocol)."""
        track = self._arrived(method, sent_at)
        if self._draining:
            raise RuntimeError(f"replica {self.replica_id} is draining")
        self.num_ongoing += 1
        begin = self._obs_begin(track)
        first_at: Optional[float] = None
        ok = False
        try:
            fn = self._resolve(method)
            out = fn(*args, **kwargs)
            if inspect.iscoroutine(out):
                out = await out
            if inspect.isasyncgen(out):
                async for chunk in out:
                    if first_at is None:
                        first_at = time.monotonic()
                    yield chunk
            elif inspect.isgenerator(out):
                for chunk in out:
                    if first_at is None:
                        first_at = time.monotonic()
                    yield chunk
                    await asyncio.sleep(0)  # keep the actor loop responsive
            else:
                first_at = time.monotonic()
                yield out
            ok = True
            if track is not None:
                # this return is the reply that carries the stream's end
                if track.ended_at is None:
                    track.ended_at = time.monotonic()
                track.finished()
        finally:
            self.num_ongoing -= 1
            self.num_processed += 1
            self._obs_end(begin, first_token_at=first_at, ok=ok,
                          window=method is None)

    async def cancel_stream(self, stream_id: str) -> bool:
        """Client abandoned the stream (``stream(timeout_s=...)`` hit its
        deadline): drop the buffer and stop the generator so drain()
        never waits on chunks nobody will claim.  The tombstone covers
        both orderings — a still-running handler consumes it in its
        finally, a not-yet-started one at registration; a finished
        stream (done flag True) needs only the pops."""
        now = time.monotonic()
        # prune tombstones nobody consumed (cancel raced a stream that
        # had already completed and been popped — its fresh-uuid id will
        # never be seen again); 120s far exceeds any legitimate gap
        # between a cancel and the handler's finally
        for sid, ts in list(self._cancelled_streams.items()):
            if now - ts > 120.0:
                self._cancelled_streams.pop(sid, None)
        done = self._stream_done.get(stream_id)
        self._forget(stream_id)
        if done is not True:
            self._cancelled_streams[stream_id] = now
        return True

    def _forget(self, stream_id: str, delivered: bool = False):
        """Drop every trace of a stream: its end was ``delivered``, or its
        caller gave it up.  Either way nothing more will be taken: the
        track books the end's delivery and stamps what it was left to."""
        self._streams.pop(stream_id, None)
        self._stream_done.pop(stream_id, None)
        track = self._stream_tracks.pop(stream_id, None)
        if track is not None:
            track.finished(delivered)

    async def next_chunks(self, stream_id: str, cursor: int) -> tuple:
        """Poll a stream: returns (new_chunks, next_cursor, done)."""
        acct, track = self.account, self._stream_tracks.get(stream_id)
        acct.polls += 1
        if track is not None and not self._stream_done.get(stream_id, True):
            acct.polls_before_end += 1
            track.polls_before_end += 1
        for _ in range(200):  # long-poll up to ~2s per call
            buf = self._streams.get(stream_id)
            if buf is None:
                raise KeyError(f"unknown stream {stream_id}")
            if len(buf) > cursor:
                chunks = buf[cursor:]
                done = self._stream_done.get(stream_id, False)
                nxt = cursor + len(chunks)
                if track is not None:
                    track.taken(cursor, nxt)
                if done and nxt == len(buf):
                    self._forget(stream_id, delivered=True)
                return chunks, nxt, done
            if self._stream_done.get(stream_id, False):
                self._forget(stream_id, delivered=True)
                return [], cursor, True
            await asyncio.sleep(0.01)
        acct.polls_empty += 1
        return [], cursor, False

    # ------------------------------------------------------------ lifecycle

    def _apply_user_config(self, user_config: Any):
        target = self.callable if self.callable is not None else None
        if target is not None and hasattr(target, "reconfigure"):
            target.reconfigure(user_config)

    async def reconfigure(self, user_config: Any) -> bool:
        self._apply_user_config(user_config)
        return True

    async def health_check(self) -> Dict[str, Any]:
        # User-defined health check hooks in when present (reference:
        # replica.py check_health).
        target = self.callable
        if target is not None and hasattr(target, "check_health"):
            res = target.check_health()
            if inspect.iscoroutine(res):
                await res
        # SLO heartbeat piggyback: the rolling TTFT percentiles + queue
        # depth ride the health check the controller already runs — no
        # extra RPC, and the controller aggregates per deployment.
        out = {"ongoing": self.num_ongoing, "processed": self.num_processed,
               "draining": self._draining,
               "slo": obs.slo_snapshot(self.deployment_name,
                                       self.num_ongoing)}
        # Prefix-cache digest piggyback (cache-aware routing): deployments
        # exposing prefix_digest() (LLMServer over a paged engine) ship a
        # bounded set of first-page block hashes the router can score
        # candidates against.  A broken hook must not fail the health
        # check — routing just falls back to pure p2c for this replica.
        if target is not None and hasattr(target, "prefix_digest"):
            try:
                out["prefix"] = target.prefix_digest()
            except Exception:
                out["prefix"] = None
        return out

    async def queue_len(self) -> int:
        return self.num_ongoing

    async def drain(self, timeout_s: float = 10.0) -> bool:
        """Stop accepting new requests; wait for ongoing ones to finish AND
        for buffered streaming chunks to be fully claimed — killing a replica
        whose client is still polling next_chunks() would truncate the
        stream mid-flight."""
        self._draining = True
        deadline = time.monotonic() + timeout_s
        while ((self.num_ongoing > 0 or self._streams)
               and time.monotonic() < deadline):
            await asyncio.sleep(0.05)
        return self.num_ongoing == 0 and not self._streams
