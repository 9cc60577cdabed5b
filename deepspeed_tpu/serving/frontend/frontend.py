"""ServingFrontend: the thread-safe control plane between many callers
and one ``ServingEngine``.

PRs 1-2 built a fast continuous-batching core, but it is a synchronous,
single-caller loop: ``run()`` owns the engine until it drains. A serving
tier needs the opposite shape — many concurrent callers, each getting an
incremental token stream, with admission shaped against priorities and
SLOs instead of arrival order. This module adds that shape without
touching the device programs:

* ``ServingFrontend.submit(prompt, *, priority, slo_ttft_s, deadline_s)``
  returns a :class:`StreamHandle` immediately from any thread;
* one background **engine-driver thread** owns every engine/scheduler
  touch (the core stays single-threaded by construction) and runs the
  same double-buffered chunk loop ``run()`` uses, via
  ``ServingEngine.pump()``;
* tokens stream to handles as each decode chunk retires (blocking
  iterator or non-blocking ``poll``), at chunk granularity — one
  delivery per ``decode_chunk`` tokens;
* ``cancel()`` frees the slot within one chunk through the engine's
  host-event patch path; ``close()`` drains in-flight work; a driver
  crash hands every outstanding handle to the fleet ``on_crash`` hook
  for replay on a survivor (``adopt`` re-prefills prompt + emitted
  tokens and dedups on emitted-token count), or resolves it ``error``
  when no hook/survivor exists — callers never hang;
* admission decisions (priority ordering, deadline-feasibility shedding,
  per-tenant rate limits) live in :mod:`.admission`; per-request spans
  and latency histograms in :mod:`.tracing`.

Terminal handle statuses: ``done | cancelled | rejected | error |
expired`` (``expired`` = admitted but its deadline passed mid-stream —
distinguished from ``rejected``, which never consumed device time).

Granularity contract: the driver observes the engine only at chunk
boundaries, so cancellation and deadline expiry take effect within one
decode chunk (up to ``decode_chunk - 1`` tokens of device work are
wasted, never delivered), and streamed tokens arrive in bursts of up to
``decode_chunk``.
"""

from __future__ import annotations

import threading
import time
from typing import Any, Dict, List, Optional, Sequence, Union

import numpy as np

from ...analysis import locks
from ...telemetry import core as telemetry
from ...telemetry.flight_recorder import FlightRecorder
from ...telemetry.journey import new_trace_id
from ...utils.logging import logger
from ..engine import MigrationError
from ..scheduler import Request
from .admission import (AdmissionConfig, AdmissionController,
                        ChunkThroughputEstimator, PRIORITY_NORMAL,
                        REJECT_FRONTEND_CLOSED, Ticket)
from .tracing import TraceLog

#: statuses after which a handle will never change again
TERMINAL_STATUSES = ("done", "cancelled", "rejected", "error", "expired")

#: versioned wire schemas (the transport serializes these verbatim)
LOAD_SCHEMA = "dstpu-load-v1"
SNAPSHOT_SCHEMA = "dstpu-snapshot-v1"


def _name_os_thread(name: str) -> None:
    """Give the calling thread its Python name at the OS too (Linux keeps
    15 characters): the profiler labels a host thread's line by that
    name, and Python before 3.14 leaves every thread named after the
    process. Where /proc is not writable the line stays as it was."""
    try:
        with open(f"/proc/self/task/{threading.get_native_id()}/comm",
                  "w") as f:
            f.write(name)
    except OSError:
        pass


class StreamHandle:
    """One caller's view of one request: a thread-safe incremental token
    stream plus the terminal status. Produced by
    :meth:`ServingFrontend.submit`; all methods are safe from any
    thread."""

    def __init__(self, request: Request, frontend: "ServingFrontend", *,
                 tenant: str, priority: int,
                 slo_ttft_s: Optional[float], submit_t: float,
                 trace_id: Optional[str] = None):
        self._request = request
        self._frontend = frontend
        # the ORIGINAL prompt and token budget, immutable for the
        # handle's lifetime: crash replay rewrites the Request's prompt
        # to prompt+emitted and shrinks its budget, so caller-facing
        # views (output_ids, request_snapshot) must read these instead
        self._prompt = np.asarray(request.prompt, np.int32)
        self._max_new_tokens = int(request.max_new_tokens)
        self.tenant = tenant
        self.priority = priority
        self.slo_ttft_s = slo_ttft_s
        self.submit_t = submit_t
        self.trace_id = trace_id       # distributed journey id (immutable)
        self._cond = locks.make_condition("frontend.stream_handle")
        self._tokens: List[int] = []
        self._cursor = 0               # poll()/iterator read position
        self._status: Optional[str] = None
        self._reject_reason: Optional[str] = None
        self._error: Optional[str] = None
        # driver-thread-only bookkeeping (never touched by callers)
        self._ticket: Optional[Ticket] = None
        self._pushed = 0               # tokens handed to _push so far
        self._lane_marked = False
        self._prefill_marked = False

    # ----------------------------------------------------- driver side
    def _push(self, tokens: Sequence[int]) -> None:
        with self._cond:
            if self._status is not None:
                return                 # terminal: late tokens are dropped
            self._tokens.extend(int(t) for t in tokens)
            self._cond.notify_all()

    def _resolve(self, status: str, *, reject_reason: Optional[str] = None,
                 error: Optional[str] = None) -> None:
        with self._cond:
            if self._status is not None:
                return                 # first terminal status wins
            self._status = status
            self._reject_reason = reject_reason
            self._error = error
            self._cond.notify_all()

    # ----------------------------------------------------- caller side
    @property
    def uid(self) -> int:
        return self._request.uid

    @property
    def status(self) -> str:
        """``"pending"`` until terminal, then one of
        :data:`TERMINAL_STATUSES`."""
        with self._cond:
            return self._status or "pending"

    @property
    def done(self) -> bool:
        with self._cond:
            return self._status is not None

    @property
    def reject_reason(self) -> Optional[str]:
        with self._cond:
            return self._reject_reason

    @property
    def error(self) -> Optional[str]:
        with self._cond:
            return self._error

    @property
    def tokens(self) -> List[int]:
        """All tokens streamed so far (copy; does not consume the
        ``poll``/iterator cursor)."""
        with self._cond:
            return list(self._tokens)

    @property
    def output_ids(self) -> np.ndarray:
        """prompt + streamed tokens — the ``Request.output_ids``
        contract, so streamed results compare bit-for-bit against
        ``ServingEngine.run``."""
        with self._cond:
            toks = np.asarray(self._tokens, np.int32)
        return np.concatenate([self._prompt, toks])

    def poll(self) -> List[int]:
        """Non-blocking: tokens that arrived since the last
        ``poll``/iteration step (empty list when none)."""
        with self._cond:
            new = self._tokens[self._cursor:]
            self._cursor = len(self._tokens)
            return [int(t) for t in new]

    def __iter__(self):
        """Blocking token stream; ends when the request reaches a
        terminal status (after yielding every delivered token)."""
        while True:
            with self._cond:
                while self._cursor >= len(self._tokens) and \
                        self._status is None:
                    self._cond.wait()
                if self._cursor < len(self._tokens):
                    tok = int(self._tokens[self._cursor])
                    self._cursor += 1
                else:
                    return
            yield tok

    def result(self, timeout: Optional[float] = None) -> str:
        """Block until terminal; returns the terminal status. Raises
        ``TimeoutError`` if the deadline passes first."""
        with self._cond:
            if not self._cond.wait_for(lambda: self._status is not None,
                                       timeout):
                raise TimeoutError(
                    f"request uid={self.uid} not terminal after "
                    f"{timeout}s (status=pending)")
            return self._status

    def cancel(self) -> None:
        """Request cancellation (idempotent, safe from any thread). The
        handle resolves to ``cancelled`` once the driver processes it —
        within one decode chunk."""
        self._frontend.cancel(self)


class ServingFrontend:
    """Thread-safe serving front end over one :class:`ServingEngine`.

    The frontend OWNS the engine's execution: after construction, no
    other code may call ``run``/``step``/``pump`` on it. A single daemon
    driver thread performs every engine and scheduler access; callers
    interact only through thread-safe ``submit``/``cancel``/``close``
    and StreamHandles.

    ``feed_depth`` bounds how many admission winners sit in the engine
    scheduler's FIFO at once (default ``max_batch``): priority decisions
    stay in the frontend's heap until the engine can actually use the
    request, keeping the priority-inversion window one batch wide.
    """

    def __init__(self, engine, *,
                 admission: Optional[AdmissionConfig] = None,
                 monitor=None,
                 feed_depth: Optional[int] = None,
                 idle_wait_s: float = 0.005,
                 emit_every_s: float = 1.0,
                 trace_keep_last: int = 256,
                 on_crash=None,
                 telemetry_label: Optional[str] = None,
                 flight_recorder: Optional[FlightRecorder] = None,
                 clock=time.monotonic):
        self._engine = engine
        self._clock = clock
        # fleet hooks: ``on_crash(frontend, salvaged_handles, exc)`` gets
        # the never-prefilled work when the driver dies (the router
        # re-homes it on survivors); ``telemetry_label`` tags every metric
        # the driver thread records with ``replica=<label>``
        self._on_crash = on_crash
        self._telemetry_label = telemetry_label
        self._controller = AdmissionController(admission, clock=clock)
        cfg = self._controller.config
        if cfg.shed_memory_infeasible and cfg.slot_tokens is None:
            # memory-aware shedding sized from the engine's own arena:
            # one slot row holds at most max_seq_len KV positions (for a
            # paged pool SMALLER than one full row per slot, the pool
            # itself is the tighter wall)
            cfg.slot_tokens = engine.max_seq_len
            pool_cap = getattr(getattr(engine, "kv", None), "allocator",
                               None)
            pool_cap = getattr(pool_cap, "pool_capacity_tokens", None)
            if pool_cap is not None:
                cfg.slot_tokens = min(cfg.slot_tokens, int(pool_cap))
        if cfg.shed_memory_infeasible and \
                getattr(engine, "kv_tier", None) is not None:
            # tiered KV: DRAM+NVMe capacity counts toward AGGREGATE
            # feasibility at a discounted rate (tier_discount) — the
            # pending queue's total KV demand may exceed the HBM pool
            # (pool_tokens) by the tier's discounted headroom. The
            # per-ticket wall stays pure-HBM (slot_tokens): an active
            # sequence's KV can never live below HBM, so a request
            # that cannot fit one slot row / the pool is infeasible
            # no matter how deep the tier is.
            rep = engine.kv.arena_report()
            bpt = max(int(rep.get("bytes_per_token", 0)), 1)
            if cfg.tier_tokens is None:
                tier = engine.kv_tier
                tier_bytes = int(tier.dram_capacity)
                if tier.nvme_capacity is not None:
                    tier_bytes += int(tier.nvme_capacity)
                cfg.tier_tokens = tier_bytes // bpt
            if cfg.pool_tokens is None:
                pool_cap = getattr(
                    getattr(engine.kv, "allocator", None),
                    "pool_capacity_tokens", None)
                cfg.pool_tokens = int(pool_cap) if pool_cap is not None \
                    else cfg.slot_tokens
        if cfg.fused_prefill_chunk is None and \
                getattr(engine, "fused_prefill", False):
            # fused chunked prefill: prompts ride the decode scan at
            # prefill_chunk tokens per step, so the admission cost model
            # counts scan steps, not bucket-weighted prompt tokens
            cfg.fused_prefill_chunk = int(engine.prefill_chunk)
        self._estimator = ChunkThroughputEstimator()
        self.tracing = TraceLog(monitor, keep_last=trace_keep_last,
                                clock=clock)
        # crash flight recorder: one bounded ring per replica; the
        # engine shares it (chunk launches/retires, slot patches) so a
        # postmortem covers both planes. Dump path of the most recent
        # crash postmortem, for the fleet router's reroute records.
        self.flight = flight_recorder if flight_recorder is not None \
            else FlightRecorder(label=telemetry_label, clock=clock)
        self.postmortem_path: Optional[str] = None
        if getattr(engine, "flight", None) is None:
            try:
                engine.flight = self.flight
            except (AttributeError, TypeError):
                pass                   # exotic engine stubs: record less
        self._feed_depth = int(feed_depth or engine.max_batch)
        self._idle_wait_s = float(idle_wait_s)
        self._emit_every_s = float(emit_every_s)
        self._last_emit_t = clock()

        self._wake = locks.make_condition("frontend.wake")
        self._cancel_requests: List[StreamHandle] = []
        # (kind, payload, box) migration events the driver thread
        # executes at its next iteration; callers block on box["done"]
        self._migrations: List[tuple] = []
        self._closing = False
        self._closed = False
        self._crashed = False
        # set by FleetRouter.retire_replica: placement has stopped and
        # /readyz reports not-ready so external balancers mirror the
        # router's exclusion while in-engine chunks retire
        self.draining = False
        self._crash_error: Optional[BaseException] = None
        # uid -> handle for requests inside the engine (driver-only)
        self._handles: Dict[int, StreamHandle] = {}
        self.n_submitted = 0

        self._thread = threading.Thread(
            target=self._drive, name="serving-frontend-driver", daemon=True)
        self._thread.start()

    # ------------------------------------------------------- public API
    def submit(self, prompt: Union[Sequence[int], np.ndarray], *,
               priority: int = PRIORITY_NORMAL,
               tenant: str = "default",
               slo_ttft_s: Optional[float] = None,
               deadline_s: Optional[float] = None,
               max_new_tokens: int = 32,
               eos_token_id: Optional[int] = None,
               trace_id: Optional[str] = None) -> StreamHandle:
        """Enqueue one generation request; returns immediately.

        ``deadline_s`` is a RELATIVE budget ("finish within this many
        seconds"), converted to the absolute clock deadline the scheduler
        tracks. ``slo_ttft_s`` is the TTFT target: it is recorded and
        scored in tracing (``slo_ttft_met``), not enforced — deadlines
        enforce. Rejections (rate limit, pending bound, dead/infeasible
        deadline, closed frontend) resolve the handle to ``rejected``
        with a machine-readable ``reject_reason``; no exception.

        ``trace_id`` is the distributed journey id; minted here when
        the caller (a fleet router) didn't already mint one."""
        now = self._clock()
        trace_id = trace_id or new_trace_id()
        req = Request(prompt=np.asarray(prompt, np.int32),
                      max_new_tokens=max_new_tokens,
                      eos_token_id=eos_token_id,
                      deadline_s=(now + deadline_s)
                      if deadline_s is not None else None,
                      trace_id=trace_id, tenant=tenant)
        handle = StreamHandle(req, self, tenant=tenant, priority=priority,
                              slo_ttft_s=slo_ttft_s, submit_t=now,
                              trace_id=trace_id)
        meta = dict(tenant=tenant, priority=priority,
                    prompt_len=req.prompt_len,
                    max_new_tokens=req.max_new_tokens,
                    slo_ttft_s=slo_ttft_s, deadline_s=req.deadline_s,
                    trace_id=trace_id, replica=self._telemetry_label)
        self.n_submitted += 1
        with self._wake:
            dead = self._closing or self._crashed
        if dead:
            self.tracing.record_rejected(req.uid, REJECT_FRONTEND_CLOSED,
                                         **meta)
            handle._resolve("rejected",
                            reject_reason=REJECT_FRONTEND_CLOSED)
            return handle
        ticket = Ticket(prompt_len=req.prompt_len,
                        max_new_tokens=req.max_new_tokens,
                        priority=priority, tenant=tenant,
                        deadline_s=req.deadline_s, slo_ttft_s=slo_ttft_s,
                        payload=handle, trace_id=trace_id)
        handle._ticket = ticket
        reason = self._controller.offer(ticket)
        if reason is not None:
            self.flight.record("reject", uid=req.uid, reason=reason,
                               trace_id=trace_id)
            self.tracing.record_rejected(req.uid, reason, **meta)
            handle._resolve("rejected", reject_reason=reason)
            return handle
        self.flight.record("submit", uid=req.uid, trace_id=trace_id,
                           tenant=tenant, priority=priority,
                           prompt_len=req.prompt_len)
        self.tracing.start(req.uid, **meta)
        self.tracing.mark(req.uid, "submitted", t=now)
        with self._wake:
            self._wake.notify()
        return handle

    def cancel(self, handle: StreamHandle) -> None:
        if handle.done:
            return
        with self._wake:
            self._cancel_requests.append(handle)
            self._wake.notify()

    def close(self, timeout: Optional[float] = None) -> None:
        """Graceful shutdown: stop accepting new work, serve everything
        already accepted to completion, then stop the driver thread.
        Idempotent. After a driver crash this just reaps the thread."""
        with self._wake:
            if self._closed:
                return
            self._closing = True
            self._wake.notify()
        self._thread.join(timeout)
        if self._thread.is_alive():
            logger.warning("serving frontend driver did not drain within "
                           f"{timeout}s; handles may still resolve late")
            return
        # post-join sweep: a submit() that raced the close can leave a
        # ticket the driver never saw
        for ticket in self._controller.drain():
            handle = ticket.payload
            self.tracing.record_rejected(
                handle.uid, REJECT_FRONTEND_CLOSED)
            handle._resolve("rejected",
                            reject_reason=REJECT_FRONTEND_CLOSED)
        self._closed = True
        self.tracing.emit()

    def __enter__(self) -> "ServingFrontend":
        return self

    def __exit__(self, *exc) -> None:
        self.close()

    # ----------------------------------------------------------- queries
    @property
    def driver_alive(self) -> bool:
        """The readiness signal ``/readyz`` (health.HealthMonitor) keys
        on: the driver thread is running and has not crashed."""
        return self._thread.is_alive() and not self.crashed

    @property
    def pending_admission(self) -> int:
        return self._controller.pending

    @property
    def max_pending(self) -> int:
        return self._controller.config.max_pending

    @property
    def crashed(self) -> bool:
        with self._wake:
            return self._crashed

    @property
    def crash_error(self) -> Optional[BaseException]:
        with self._wake:
            return self._crash_error

    def load_snapshot(self) -> Dict[str, Any]:
        """Placement inputs for a fleet router: the admission
        controller's and throughput estimator's locked snapshots plus
        the engine backlog. Engine-side numbers are read without the
        driver's cooperation, so they are approximate under concurrency
        — fine for load scoring, not for invariants.

        The dict is ``dstpu-load-v1``: plain ints/floats/strings only,
        so ``json.dumps`` round-trips it losslessly — the transport
        serves it verbatim at ``GET /v1/load``."""
        sched = self._engine.scheduler
        backlog = sum(r.max_new_tokens - len(r.tokens)
                      for r in list(sched.running.values()))
        backlog += sum(q.max_new_tokens + q.prompt_len
                       for q in list(sched.queue))
        return {
            "schema": LOAD_SCHEMA,
            "admission": self._controller.snapshot(),
            "throughput": self._estimator.snapshot(),
            "engine_backlog_tokens": int(backlog),
            "engine_queue_depth": len(sched.queue),
            "engine_running": len(sched.running),
        }

    @staticmethod
    def _handle_snapshot(handle: StreamHandle) -> Dict[str, Any]:
        """One locked read of everything replay (and a postmortem)
        needs about one handle: the ORIGINAL prompt and budget, the
        tokens emitted to the caller so far, and the sampling/admission
        parameters. The shared shape behind ``request_snapshot`` and
        the flight recorder's ``in_flight`` records.

        The dict is ``dstpu-snapshot-v1``: JSON-round-trippable by
        construction — the prompt is a plain int list, never the
        ndarray it used to leak (which ``json.dumps`` rejects), so the
        transport's ``/v1/adopt`` ships it verbatim."""
        with handle._cond:
            emitted = list(handle._tokens)
            status = handle._status or "pending"
        req = handle._request
        return {
            "schema": SNAPSHOT_SCHEMA,
            "uid": handle.uid,
            "trace_id": handle.trace_id,
            "status": status,
            "prompt": [int(t) for t in handle._prompt],
            "prompt_len": int(handle._prompt.shape[0]),
            "tokens_emitted": [int(t) for t in emitted],
            "max_new_tokens": handle._max_new_tokens,
            "sampling": {"eos_token_id": (
                             None if req.eos_token_id is None
                             else int(req.eos_token_id)),
                         "deadline_s": (None if req.deadline_s is None
                                        else float(req.deadline_s)),
                         "priority": int(handle.priority),
                         "tenant": handle.tenant,
                         "slo_ttft_s": (
                             None if handle.slo_ttft_s is None
                             else float(handle.slo_ttft_s))},
        }

    def request_snapshot(self, uid: int) -> Optional[Dict[str, Any]]:
        """Locked accessor for one outstanding request: original prompt,
        tokens emitted so far, and sampling params — the stable API
        replay and postmortems share instead of poking ``_handles``.
        Finds the handle whether it is admission-pending or inside the
        engine; returns None for unknown/finished-and-reaped uids.
        Thread-safe (dict/heap reads are locked or GIL-atomic; the
        token read locks the handle)."""
        handle = self._handles.get(uid)
        if handle is None:
            for ticket in self._controller.tickets():
                payload = ticket.payload
                if payload is not None and payload.uid == uid:
                    handle = payload
                    break
        if handle is None:
            return None
        return self._handle_snapshot(handle)

    def holds_prefix(self, key: bytes) -> bool:
        """Pure prefix-cache membership peek (no LRU touch) — the
        router's placement affinity probe, and the surface the
        transport's ``GET /v1/prefix`` serves. Covers BOTH residency
        levels: the HBM prefix cache and the demoted DRAM/NVMe tier
        (a tier-held prefix still saves the full prefill — it admits
        through an async promotion instead of a recompute). False on
        engines without a prefix cache."""
        kv = getattr(self._engine, "kv", None)
        cache = getattr(kv, "prefix_cache", None)
        if cache is None or not getattr(kv, "prefix_enabled", False):
            return False
        if key in cache:
            return True
        tier = getattr(self._engine, "kv_tier", None)
        return tier is not None and tier.holds(key)

    def fetch_prefix(self, key: bytes) -> Optional[Dict[str, Any]]:
        """Serve a peer's prefix fetch: the demoted entry's KV payload
        as a ``dstpu-prefix-v1`` bundle, or None when this replica does
        not hold it in a FETCHABLE tier. Tier entries only — the HBM
        prefix cache lives in the device pool, which only the engine
        thread may read; a warm prefix becomes fetchable once it
        demotes. Thread-safe (the tier is host-side, lock-protected),
        no driver round-trip."""
        tier = getattr(self._engine, "kv_tier", None)
        if tier is None:
            return None
        return tier.fetch_bundle(key)

    def install_prefix(self, bundle: Dict[str, Any]) -> bool:
        """Install a peer-fetched prefix bundle into the local DRAM
        tier (the receiving half of the distributed prefix cache). The
        entry promotes to HBM through the normal async path when a
        request for its prompt arrives — zero re-prefill. Thread-safe;
        False on engines without a tier or when the tier declined
        (duplicate key / closed)."""
        tier = getattr(self._engine, "kv_tier", None)
        if tier is None:
            return False
        return tier.install_bundle(bundle)

    def migration_candidates(self) -> List[int]:
        """uids of requests movable RIGHT NOW (running, fully
        prefilled, at least one emitted token, paged KV) — the set a
        rebalancer picks from. Thread-safe, approximate under
        concurrency: the driver re-checks at migrate time."""
        eng = self._engine
        can = getattr(eng, "can_migrate", None)
        if can is None:
            return []
        out: List[int] = []
        for req in list(eng.scheduler.running.values()):
            try:
                if req.uid in self._handles and can(req):
                    out.append(int(req.uid))
            except Exception:  # noqa: BLE001 — a racing retire is a no
                continue
        return out

    def migrate_out(self, uid: int, timeout: Optional[float] = 30.0):
        """Serialize and DETACH one running request: returns
        ``(bundle, handle)`` where ``bundle`` is the engine's KV +
        cursor export and ``handle`` is the caller's still-pending
        StreamHandle, released from this frontend (its engine-side
        request is cancelled, its trace segment closes ``migrated``).
        The handle keeps streaming once a target's ``migrate_in``
        re-attaches it. Runs on the driver thread (this call blocks
        until it executes); raises :class:`MigrationError` when the
        request is not migratable or the driver is gone."""
        box: Dict[str, Any] = {"done": threading.Event()}
        with self._wake:
            if self._closing or self._crashed:
                raise MigrationError("frontend is closed or crashed")
            self._migrations.append(("out", {"uid": int(uid)}, box))
            self._wake.notify()
        if not box["done"].wait(timeout):
            raise MigrationError(
                f"migrate_out uid={uid} did not execute within "
                f"{timeout}s")
        if "error" in box:
            raise MigrationError(box["error"])
        return box["bundle"], box["handle"]

    def migrate_in(self, bundle: Dict[str, Any],
                   handle: Optional[StreamHandle] = None, *,
                   migrated_from: Optional[str] = None,
                   timeout: Optional[float] = 30.0) -> StreamHandle:
        """Re-home an exported request HERE, mid-decode: lease blocks,
        scatter the bundle's KV, and join the running set — the next
        chunk continues from the migrated cursor, greedy bit-identical
        to never having moved. ``handle`` (the in-process case) is
        re-attached and keeps streaming to its caller; without one (the
        transport server case) a fresh handle is built whose delivered
        prefix is the bundle's resumed tokens. Raises
        :class:`MigrationError` when this engine cannot host the
        request (the caller re-imports at the source)."""
        box: Dict[str, Any] = {"done": threading.Event()}
        with self._wake:
            if self._closing or self._crashed:
                raise MigrationError("frontend is closed or crashed")
            self._migrations.append(
                ("in", {"bundle": bundle, "handle": handle,
                        "migrated_from": migrated_from}, box))
            self._wake.notify()
        if not box["done"].wait(timeout):
            raise MigrationError(
                f"migrate_in did not execute within {timeout}s")
        if "error" in box:
            raise MigrationError(box["error"])
        return box["handle"]

    def stats(self) -> Dict[str, Any]:
        """Control-plane counters (thread-safe, approximate under
        concurrency)."""
        return {
            "submitted": self.n_submitted,
            "pending_admission": self._controller.pending,
            "offered": self._controller.n_offered,
            "rate_limited": self._controller.n_rate_limited,
            "shed": self._controller.n_shed,
            "decode_rate_tokens_per_s": self._estimator.rate(),
            "terminal": dict(self.tracing.counters),
        }

    def drain_pending(self) -> List[StreamHandle]:
        """Graceful drain, phase one: pull every admission-pending
        ticket off this frontend (thread-safe) and return the still-live
        handles so a router can re-home them on survivors. Requests
        already inside the engine are NOT touched — their chunks retire
        naturally, which is the rest of the drain. Each returned
        handle's trace segment here closes ``rerouted``; the adopter
        re-opens the same uid/trace_id."""
        handles: List[StreamHandle] = []
        for ticket in self._controller.drain():
            handle: StreamHandle = ticket.payload
            if handle is None or handle.done:
                continue
            self.tracing.finish(handle.uid, "rerouted")
            handles.append(handle)
        return handles

    def adopt(self, handle: StreamHandle,
              rerouted_from: Optional[str] = None) -> bool:
        """Re-home a handle from a crashed or draining peer onto this
        frontend. The SAME StreamHandle keeps streaming to its caller;
        only the backend changes — the handle keeps its ``trace_id``,
        and this replica's trace segment records
        ``rerouted_from=<source replica>`` so the journey stays one
        connected story.

        Never-prefilled handles restart from scratch. Handles that
        already streamed tokens are REPLAYED: this engine re-prefills
        the original prompt + the tokens already emitted (a paged
        ``PrefixCache`` hit when a peer replayed the same stream), the
        token budget shrinks by the emitted count, and the delivery
        cursor resets so ``_push_progress`` hands the caller only
        freshly generated tokens — zero duplicates, greedy
        bit-identical to an uncrashed run. The replay is rebuilt from
        the handle's ORIGINAL prompt/budget each time, so repeated
        crashes compose. The survivor's ``submitted`` trace mark keeps
        the ORIGINAL submit time: a journey's latency clock never
        resets, so recovery delay lands in TTFT/queue-wait SLOs.

        Returns False — after resolving the handle ``rejected`` — when
        this frontend cannot take it; thread-safe."""
        if handle.done:
            return False
        req = handle._request
        emitted = handle.tokens
        n_emitted = len(emitted)
        if req.status == "done" or n_emitted >= handle._max_new_tokens \
                or (req.eos_token_id is not None
                    and n_emitted and emitted[-1] == req.eos_token_id):
            # the stream already delivered its full output — the crash
            # only stole the final status. Nothing to replay: close the
            # journey here as done.
            self.tracing.start(req.uid, trace_id=handle.trace_id,
                               replica=self._telemetry_label,
                               rerouted_from=rerouted_from)
            self.tracing.finish(req.uid, "done")
            handle._resolve("done")
            return True
        # rebuild the scheduler-side lifecycle from the handle's
        # original prompt/budget: replay prompt = prompt + emitted
        # prefix, remaining budget = original budget - emitted count
        req.prompt = handle._prompt
        req.max_new_tokens = handle._max_new_tokens
        if n_emitted:
            req.prompt = np.concatenate(
                [handle._prompt, np.asarray(emitted, np.int32)])
            req.max_new_tokens = handle._max_new_tokens - n_emitted
        req.tokens = []
        req.status = "new"
        req.slot = None
        req.submit_t = None
        req.first_token_t = None
        req.finish_t = None
        req.tenant = handle.tenant
        handle._pushed = 0
        handle._prefill_marked = False
        handle._frontend = self
        meta = dict(tenant=handle.tenant, priority=handle.priority,
                    prompt_len=req.prompt_len,
                    max_new_tokens=req.max_new_tokens,
                    slo_ttft_s=handle.slo_ttft_s, deadline_s=req.deadline_s,
                    trace_id=handle.trace_id,
                    replica=self._telemetry_label,
                    rerouted_from=rerouted_from,
                    replayed_tokens=n_emitted)
        self.n_submitted += 1
        with self._wake:
            dead = self._closing or self._crashed
        if dead:
            self.tracing.record_rejected(req.uid, REJECT_FRONTEND_CLOSED,
                                         **meta)
            handle._resolve("rejected",
                            reject_reason=REJECT_FRONTEND_CLOSED)
            return False
        ticket = Ticket(prompt_len=req.prompt_len,
                        max_new_tokens=req.max_new_tokens,
                        priority=handle.priority, tenant=handle.tenant,
                        deadline_s=req.deadline_s,
                        slo_ttft_s=handle.slo_ttft_s, payload=handle,
                        trace_id=handle.trace_id)
        handle._ticket = ticket
        reason = self._controller.offer(ticket)
        if reason is not None:
            self.tracing.record_rejected(req.uid, reason, **meta)
            handle._resolve("rejected", reject_reason=reason)
            return False
        self.flight.record("adopt", uid=req.uid,
                           trace_id=handle.trace_id,
                           rerouted_from=rerouted_from,
                           replayed_tokens=n_emitted)
        self.tracing.start(req.uid, **meta)
        self.tracing.mark(req.uid, "submitted", t=handle.submit_t)
        with self._wake:
            self._wake.notify()
        return True

    # ------------------------------------------------------ driver loop
    def _drive(self) -> None:
        _name_os_thread(threading.current_thread().name)
        try:
            with telemetry.replica_label(self._telemetry_label):
                while self._drive_once():
                    pass
        except BaseException as e:  # noqa: BLE001 — converted to results
            self._fail_all(e)

    def _requests_waiting(self) -> bool:
        eng = self._engine
        return bool(self._controller.pending or eng.scheduler.has_work()
                    or eng.chunk_in_flight)

    def _drive_once(self) -> bool:
        with self._wake:
            if not (self._cancel_requests or self._migrations
                    or self._closing or self._requests_waiting()):
                with telemetry.span("frontend/idle_wait"):
                    self._wake.wait(self._idle_wait_s)
            cancels, self._cancel_requests = self._cancel_requests, []
            migrations, self._migrations = self._migrations, []
            closing = self._closing
        if not (cancels or migrations or closing
                or self._requests_waiting()):
            self._maybe_emit()
            return True
        # an iteration that found work is one ``frontend/drive`` span: its
        # time less the device waits inside it is the driver's own
        with telemetry.span("frontend/drive"):
            return self._drive_work(cancels, migrations, closing)

    def _drive_work(self, cancels, migrations, closing: bool) -> bool:
        eng = self._engine
        for handle in cancels:
            self._do_cancel(handle)
        for kind, payload, box in migrations:
            try:
                if kind == "out":
                    self._do_migrate_out(payload["uid"], box)
                else:
                    self._do_migrate_in(payload["bundle"],
                                        payload["handle"],
                                        payload["migrated_from"], box)
            except Exception as e:  # noqa: BLE001 — caller unblocks
                box["error"] = f"{type(e).__name__}: {e}"
            finally:
                box["done"].set()
        with telemetry.span("frontend/feed"):
            self._feed()
        if eng.scheduler.has_work() or eng.chunk_in_flight:
            tokens_before = eng.metrics.tokens_out
            inline_before = getattr(eng, "inline_prefill_tokens", 0)
            t0 = time.perf_counter()
            finished = eng.pump()
            dt = time.perf_counter() - t0
            produced = eng.metrics.tokens_out - tokens_before
            chunk = self._controller.config.fused_prefill_chunk
            if chunk:
                # inline prompt chunks consume scan steps exactly like
                # decode tokens do: fold them into the throughput EWMA
                # in the same decode-token-equivalent unit the cost
                # model bills, or a prefill-heavy chunk would read as a
                # throughput collapse and shed feasible deadlines
                inline = getattr(eng, "inline_prefill_tokens", 0) \
                    - inline_before
                if inline > 0:
                    produced += -(-inline // chunk)
            self._estimator.record(produced, dt)
            rate = self._estimator.rate()
            if rate is not None:
                telemetry.gauge("admission/ewma_tokens_per_s", float(rate))
            telemetry.gauge("frontend/queue_depth",
                            float(self._controller.pending))
            with telemetry.span("frontend/deliver",
                                n_finished=len(finished)):
                self._deliver(finished)
            # the scheduler's finished list is an append-only log; the
            # frontend is its only consumer, so trim it here or a
            # long-running server grows without bound
            eng.scheduler.finished.clear()
        self._maybe_emit()
        if closing:
            # a caller may have appended a cancel since the drain above
            # dropped the wake lock — re-check under it before exiting
            with self._wake:
                cancels_drained = not self._cancel_requests
            if cancels_drained and not (self._controller.pending
                                        or eng.scheduler.has_work()
                                        or eng.chunk_in_flight
                                        or self._handles):
                return False
        return True

    def _feed(self) -> None:
        """Move admission winners into the engine scheduler, keeping its
        FIFO at most ``feed_depth`` deep so priority order keeps ruling
        the backlog."""
        eng = self._engine
        sched = eng.scheduler
        room = self._feed_depth - len(sched.queue)
        if room <= 0 or self._controller.pending == 0:
            return
        cfg = self._controller.config
        backlog = sum(r.max_new_tokens - len(r.tokens)
                      for r in sched.running.values())
        chunk = cfg.fused_prefill_chunk
        if chunk:
            backlog += sum(
                q.max_new_tokens + -(-q.prompt_len // chunk)
                for q in sched.queue)
            # mid-prompt lanes still owe their remaining inline chunks
            # before they emit a single decode token
            for slot, done in getattr(eng, "_pf_consumed", {}).items():
                req = sched.running.get(slot)
                if req is not None and done < req.prompt_len:
                    backlog += -(-(req.prompt_len - done) // chunk)
        else:
            w = cfg.prefill_token_weight
            backlog += sum(q.max_new_tokens + q.prompt_len * w
                           for q in sched.queue)
        admits, sheds = self._controller.pop(
            room=room, rate=self._estimator.rate(), backlog_tokens=backlog)
        for ticket, reason in sheds:
            self.flight.record("shed", uid=ticket.payload.uid,
                               reason=reason, trace_id=ticket.trace_id)
            self._resolve_rejected(ticket, reason)
        for ticket in admits:
            handle: StreamHandle = ticket.payload
            req = handle._request
            eng.submit(req)
            if req.status == "rejected":      # scheduler-side reject
                self._resolve_rejected(ticket, req.reject_reason)
            else:
                self._handles[req.uid] = handle
                self.flight.record("admit", uid=req.uid,
                                   trace_id=ticket.trace_id)
                self.tracing.mark(req.uid, "admitted")

    def _resolve_rejected(self, ticket: Ticket, reason: str) -> None:
        handle: StreamHandle = ticket.payload
        self.tracing.finish(handle.uid, "rejected", reject_reason=reason)
        handle._resolve("rejected", reject_reason=reason)

    def _push_progress(self, req: Request,
                       handle: Optional[StreamHandle] = None) -> None:
        handle = handle or self._handles.get(req.uid)
        if handle is None:
            return
        if not handle._lane_marked and req.admit_t is not None:
            # the scheduler's stamps are on the same monotonic timebase
            # as the frontend clock
            self.tracing.mark(req.uid, "lane", t=req.admit_t)
            handle._lane_marked = True
        if not handle._prefill_marked and req.first_token_t is not None:
            # prefill completion = the first sampled token's scheduler
            # timestamp
            self.tracing.mark(req.uid, "prefill", t=req.first_token_t)
            handle._prefill_marked = True
        n = len(req.tokens)
        if n > handle._pushed:
            new = req.tokens[handle._pushed:n]
            handle._pushed = n
            self.tracing.chunk(req.uid, len(new))
            handle._push(new)

    def _deliver(self, finished: List[Request]) -> None:
        eng = self._engine
        for req in list(eng.scheduler.running.values()):
            self._push_progress(req)
        for req in finished:
            handle = self._handles.pop(req.uid, None)
            if handle is None:
                continue              # cancelled earlier this iteration
            self._push_progress(req, handle)
            self.tracing.finish(req.uid, req.status)
            handle._resolve(req.status)

    def _do_migrate_out(self, uid: int, box: Dict[str, Any]) -> None:
        """Driver-side half of :meth:`migrate_out`: flush delivered
        tokens (the handle's emitted prefix must equal the request's
        committed tokens — the bundle's resumed-token count), export
        the KV bundle, then detach: pop the handle, cancel the
        engine-side request (slot + blocks free within this
        iteration), and close the trace segment ``migrated``."""
        eng = self._engine
        handle = self._handles.get(uid)
        if handle is None:
            box["error"] = f"uid {uid} is not inside this engine"
            return
        req = handle._request
        self._push_progress(req, handle)
        bundle = eng.export_request(req)       # raises MigrationError
        self._handles.pop(uid, None)
        eng.cancel(req)
        self.flight.record("migrate_out", uid=uid,
                           trace_id=handle.trace_id,
                           n_tokens=len(bundle["tokens"]),
                           kv_bytes=bundle["kv_bytes"])
        self.tracing.finish(uid, "migrated")
        box["bundle"] = bundle
        box["handle"] = handle

    def _do_migrate_in(self, bundle: Dict[str, Any],
                       handle: Optional[StreamHandle],
                       migrated_from: Optional[str],
                       box: Dict[str, Any]) -> None:
        """Driver-side half of :meth:`migrate_in`: import the bundle
        into the engine (slot + blocks + cursor), then attach the
        caller's handle (or mint one for a transport-server stream) so
        delivery resumes exactly past the resumed-token prefix."""
        eng = self._engine
        req = eng.import_request(bundle)       # raises MigrationError
        resumed = len(req.tokens)
        if handle is None:
            handle = StreamHandle(
                req, self, tenant=req.tenant, priority=PRIORITY_NORMAL,
                slo_ttft_s=None, submit_t=self._clock(),
                trace_id=req.trace_id)
            with handle._cond:
                # the resumed prefix was already delivered at the
                # source; keep it in the buffer so absolute token
                # indices (the wire's dedup key) stay continuous, and
                # park the cursor past it so a server-side stream
                # starts at the first fresh token
                handle._tokens = [int(t) for t in req.tokens]
                handle._cursor = resumed
        handle._request = req
        handle._frontend = self
        handle._ticket = None
        handle._pushed = resumed
        handle._prefill_marked = True
        self._handles[req.uid] = handle
        self.n_submitted += 1
        meta = dict(tenant=handle.tenant, priority=handle.priority,
                    prompt_len=req.prompt_len,
                    max_new_tokens=req.max_new_tokens,
                    slo_ttft_s=handle.slo_ttft_s,
                    deadline_s=req.deadline_s,
                    trace_id=handle.trace_id,
                    replica=self._telemetry_label,
                    migrated_from=migrated_from,
                    resumed_tokens=resumed)
        self.tracing.start(req.uid, **meta)
        self.tracing.mark(req.uid, "submitted", t=handle.submit_t)
        self.tracing.mark(req.uid, "admitted")
        self.flight.record("migrate_in", uid=req.uid,
                           trace_id=handle.trace_id,
                           migrated_from=migrated_from,
                           resumed_tokens=resumed)
        box["handle"] = handle

    def _do_cancel(self, handle: StreamHandle) -> None:
        if handle.done:
            return
        self.flight.record("cancel", uid=handle.uid,
                           trace_id=handle.trace_id)
        ticket = handle._ticket
        if ticket is not None and self._controller.remove(ticket):
            # never reached the engine: no slot, no device work
            self.tracing.finish(handle.uid, "cancelled")
            handle._resolve("cancelled")
            return
        req = handle._request
        if self._engine.cancel(req):
            self._handles.pop(req.uid, None)
            self._push_progress(req, handle)
            self.tracing.finish(handle.uid, "cancelled")
            handle._resolve("cancelled")
        # else: the request reached a terminal state in the scheduler
        # already — the regular _deliver path resolves the handle

    def _maybe_emit(self) -> None:
        now = self._clock()
        if now - self._last_emit_t >= self._emit_every_s:
            self._last_emit_t = now
            sched = getattr(self._engine, "scheduler", None)
            self.flight.record(
                "snapshot",
                pending_admission=self._controller.pending,
                queue_depth=len(sched.queue) if sched is not None else 0,
                running=len(sched.running) if sched is not None else 0,
                handles=len(self._handles))
            self.tracing.emit()

    def _fail_all(self, exc: BaseException) -> None:
        """Driver crash: every outstanding request — pending admission,
        queued, running — either reroutes to a survivor or resolves to a
        structured ``error`` result so no caller blocks forever, then
        the frontend is marked dead (new submits reject with
        ``frontend_closed``).

        With an ``on_crash`` hook installed, EVERY live handle is
        salvageable: admission-pending and engine-queued requests
        restart from scratch on a survivor, and requests that already
        prefilled or streamed tokens are REPLAYED — the handle carries
        the original prompt plus every emitted token, which is all a
        survivor's ``adopt()`` needs to re-prefill and resume the
        stream with zero duplicates (the device KV died with the
        replica; the journey did not). Only cancel-pending handles are
        excluded — the caller already gave up on them.

        Before resolving ANYTHING the flight recorder dumps a
        postmortem (``self.postmortem_path``) whose ``in_flight`` list
        is exactly the handle set this crash is about to hand off for
        reroute or resolve ``error``."""
        msg = f"{type(exc).__name__}: {exc}"
        logger.error(f"serving frontend driver crashed: {msg}")
        with self._wake:
            self._crashed = True
            self._crash_error = exc
            cancels, self._cancel_requests = self._cancel_requests, []
            migrations, self._migrations = self._migrations, []
        for _kind, _payload, box in migrations:
            box["error"] = f"driver crashed: {msg}"
            box["done"].set()
        cancel_uids = {h.uid for h in cancels}
        salvaged: List[StreamHandle] = []
        for ticket in self._controller.drain():
            if ticket.payload.uid not in cancel_uids:
                salvaged.append(ticket.payload)
        # engine-queued requests were fed but never admitted to a slot:
        # host-only state, safe to replay elsewhere (scheduler data is
        # driver-owned and this IS the driver thread, post-crash)
        sched = getattr(self._engine, "scheduler", None)
        if sched is not None:
            for req in list(sched.queue):
                handle = self._handles.pop(req.uid, None)
                if handle is not None and handle.uid not in cancel_uids:
                    salvaged.append(handle)
            sched.queue.clear()
        # running handles (admitted, possibly mid-stream): flush any
        # recorded-but-unpushed tokens first so the handle's emitted
        # prefix matches what the device actually committed — the
        # replay prompt is built from exactly this prefix
        running: List[StreamHandle] = []
        for uid, handle in list(self._handles.items()):
            try:
                self._push_progress(handle._request, handle)
            except Exception:  # noqa: BLE001 — salvage beats bookkeeping
                pass
            if uid not in cancel_uids:
                running.append(handle)
        # ---- postmortem: capture the in-flight set pre-resolution ----
        in_flight: List[Dict[str, Any]] = []
        seen: set = set()
        for disposition, group in (("salvageable", salvaged),
                                   ("salvageable", running),
                                   ("cancel_pending", cancels)):
            for handle in group:
                if handle.uid in seen:
                    continue
                seen.add(handle.uid)
                in_flight.append({
                    "uid": handle.uid,
                    "trace_id": handle.trace_id,
                    "status": handle.status,
                    "n_tokens": len(handle.tokens),
                    "prompt_len": int(handle._prompt.shape[0]),
                    "max_new_tokens": handle._max_new_tokens,
                    "disposition": disposition})
        slot_uids = {}
        if sched is not None:
            slot_uids = {req.slot: req.uid
                         for req in list(sched.running.values())
                         if req.slot is not None}
        try:
            self.postmortem_path = self.flight.dump(
                reason="driver_crash", error=msg, in_flight=in_flight,
                slot_uids=slot_uids,
                extra={"n_salvageable": len(salvaged) + len(running),
                       "n_running": len(running),
                       "pending_admission": self._controller.pending})
        except Exception as dump_exc:  # noqa: BLE001 — never block drain
            logger.error(f"flight recorder dump failed: {dump_exc}")
        # hand never-prefilled work first: survivors fill slots with
        # cheap restarts while the replays re-prefill behind them
        to_hand: List[StreamHandle] = salvaged + running
        handed: List[StreamHandle] = []
        if self._on_crash is not None and to_hand:
            try:
                handed = list(to_hand)
                self._on_crash(self, list(to_hand), exc)
                to_hand = []
            except Exception as hook_exc:  # noqa: BLE001 — fall back
                handed = []
                logger.error(
                    f"crash re-route hook failed ({hook_exc}); resolving "
                    f"{len(to_hand)} salvaged handles as error")
        # close this replica's trace segment for every handle the hook
        # re-homed: terminal status ``rerouted`` links the journey's next
        # segment (the survivor re-opens the same uid/trace_id)
        for handle in handed:
            self.tracing.finish(handle.uid, "rerouted", error=msg)
        for handle in to_hand:
            self.tracing.finish(handle.uid, "error", error=msg)
            handle._resolve("error", error=msg)
        self._handles.clear()
        for handle in cancels:
            self.tracing.finish(handle.uid, "error", error=msg)
            handle._resolve("error", error=msg)
