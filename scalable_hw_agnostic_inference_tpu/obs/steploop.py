"""Engine step telemetry: the per-step signals behind the control plane.

The serving layer's request counter tells KEDA *how much* traffic arrived;
it says nothing about *why* latency moved. The engine records, every
``step()``, the numbers that explain it — running/waiting occupancy,
KV-page utilization, preemptions, speculative acceptance, post-warm
(bucket-miss) recompiles — plus dependency-free TTFT/TPOT/queue-wait
histograms with explicit buckets. ``serve.metrics`` exports all of it as
real Prometheus histograms/gauges on ``/metrics`` and as JSON lines, so the
autoscaler and the cova failover controller scale on queue depth and KV
pressure instead of raw request rate (SURVEY.md §5: "metrics ARE the
control plane", now with engine-grade signals).

Layering: the engine must not import the serve package, so everything here
is stdlib-only; the serve layer adapts these snapshots into exposition
formats.
"""

from __future__ import annotations

import contextvars
import queue
from bisect import bisect_left
import threading
import time
from collections import deque
from typing import Any, Dict, List, Optional, Sequence, Tuple

from .trace import Trace, annotate

#: the recurrent KINDS a model's slot state can be
#: (``LlamaConfig.state_kind``): each is an attribute here, an entry of the
#: snapshot and a counter family (``count_recurrent``)
RECURRENT_KINDS = ("kda", "ssm", "conv")

#: explicit histogram bounds (seconds). TTFT includes queue time, so its
#: range reaches minutes; TPOT is per-token decode pace (milliseconds).
TTFT_BUCKETS = (0.01, 0.025, 0.05, 0.1, 0.25, 0.5, 1.0, 2.5, 5.0, 10.0,
                30.0, 60.0)
TPOT_BUCKETS = (0.001, 0.0025, 0.005, 0.01, 0.025, 0.05, 0.1, 0.25, 0.5,
                1.0)
QUEUE_WAIT_BUCKETS = (0.001, 0.005, 0.01, 0.05, 0.1, 0.25, 0.5, 1.0, 2.5,
                      5.0, 10.0, 30.0, 60.0)
#: inter-step device gap: host time between fetching one decode step's
#: results and enqueueing the next decode dispatch — the serial host work
#: the device sits idle behind. The async pipeline (SHAI_ASYNC_DECODE)
#: dispatches ahead of the fetch, so steady steps observe (clamped) zero;
#: lock-step observes the full marshal+bookkeeping gap every step.
STEP_GAP_BUCKETS = (0.0001, 0.00025, 0.0005, 0.001, 0.0025, 0.005, 0.01,
                    0.025, 0.05, 0.1, 0.5)
#: submit-to-intake wait: ``EngineLoop.submit`` on the caller's thread to
#: ``add_request`` on the loop thread, which runs between steps only — a
#: request that arrives during a prefill program waits out the program here
INTAKE_WAIT_BUCKETS = (0.0005, 0.001, 0.0025, 0.005, 0.01, 0.025, 0.05, 0.1,
                       0.25, 0.5, 1.0, 5.0)
#: a streamed token's hops out of the program (commit -> taken by its
#: stream -> handed on encoded -> written to the socket), their sum,
#: and a finished request's wait for its last byte. 50 us to 10 s: the mean
#: is tens of microseconds a hop on a quiet host and the tail is the finding
STREAM_BUCKETS = (0.00005, 0.0001, 0.00025, 0.0005, 0.001, 0.0025, 0.005,
                  0.01, 0.025, 0.05, 0.1, 0.25, 0.5, 1.0, 2.5, 5.0, 10.0)
#: a request's way in: the ASGI app's first stamp to ``EngineLoop.submit``
#: (body read, JSON, the admission gate, the lane, tokenising the prompt)
INGRESS_BUCKETS = (0.0005, 0.001, 0.0025, 0.005, 0.01, 0.025, 0.05, 0.1,
                   0.25, 0.5, 1.0, 2.5, 5.0, 10.0)
#: the phases of the ``engine-loop`` thread, in the order the event path
#: walks them. Exactly one is open at any instant (``phase_enter`` closes
#: the open one at the clock read that opens the next), so their seconds
#: tile the thread's time and their profiler spans never nest — the trace
#: reader names a device gap by the host span that overlaps it most, and an
#: enclosing span would name them all.
PHASES = ("loop.idle", "loop.intake", "engine.fetch", "engine.apply",
          "engine.admit", "engine.prefill", "engine.chunk", "engine.verify",
          "engine.decode", "engine.marshal", "engine.commit",
          "engine.record", "loop.resolve")
#: the phases that never wait for the device or for a queue: their wall
#: time (``phase_cpu_wall_s``) less their CPU time (``phase_cpu_s``) is time
#: the thread wanted to run and did not (the interpreter lock, the scheduler)
NON_WAITING_PHASES = ("engine.admit", "engine.marshal", "engine.commit",
                      "engine.apply", "engine.record", "loop.intake",
                      "loop.resolve")
#: the thread's CPU clock is read at the boundaries of one step in this
#: many (and of the loop's phases behind it): ``time.thread_time()`` is a
#: system call, 0.35 us on a workstation and 5.8 us on the chip's host,
#: where eleven boundaries a step would cost the four-chip cell's 8 ms
#: cycle 0.9% (PERF.md, PR 38)
CPU_SAMPLE_EVERY = 8
#: engine phase -> the field of the step's ring record its milliseconds
#: add to (the four dispatch families share one)
_STEP_FIELD = {"engine.fetch": "fetch_ms", "engine.apply": "apply_ms",
               "engine.admit": "admit_ms", "engine.marshal": "marshal_ms",
               "engine.commit": "commit_ms", "engine.prefill": "dispatch_ms",
               "engine.chunk": "dispatch_ms", "engine.verify": "dispatch_ms",
               "engine.decode": "dispatch_ms"}
_STEP_FIELDS = tuple(dict.fromkeys(_STEP_FIELD.values()))
#: a step is STALLED where its duration passes ten times the ring's median,
#: and this floor: a 47 ms chunk step beside 3 ms decode steps is the
#: schedule's, not a stall, and the shortest stop worth a name is 0.1 s
STALL_FLOOR_S = 0.1
STALL_TIMES_MEDIAN = 10.0
#: bounded tenant-label cardinality for the per-tenant instruments: at
#: most this many distinct tenants get their own label; later arrivals
#: collapse into "other" so a hostile client minting tenant names cannot
#: grow the metric series set (or this object) without bound. Matches the
#: ledger's SHAI_QOS_MAX_TENANTS discipline (resilience.qos).
MAX_TENANT_LABELS = 32
_OTHER_TENANT = "other"
_DEFAULT_TENANT = "default"


class BucketHistogram:
    """Thread-safe fixed-bucket histogram (Prometheus-shaped: cumulative
    bucket counts + sum + count), dependency-free so the engine can own it."""

    def __init__(self, bounds: Sequence[float],
                 lock: Optional[threading.Lock] = None):
        """``lock``: one shared by several histograms whose owner observes
        them together (``observe_locked`` under one acquisition)."""
        self.bounds: Tuple[float, ...] = tuple(sorted(float(b)
                                                      for b in bounds))
        if not self.bounds:
            raise ValueError("histogram needs at least one bucket bound")
        self._counts = [0] * (len(self.bounds) + 1)  # last = +Inf
        self._sum = 0.0
        self._n = 0
        self._lock = lock if lock is not None else threading.Lock()

    def observe(self, v: float) -> None:
        i = bisect_left(self.bounds, v)   # the first bound v is not over
        with self._lock:
            self._counts[i] += 1
            self._sum += v
            self._n += 1

    def observe_locked(self, v: float) -> None:
        """``observe`` for a caller that holds this histogram's lock."""
        self._counts[bisect_left(self.bounds, v)] += 1
        self._sum += v
        self._n += 1

    @property
    def count(self) -> int:
        with self._lock:
            return self._n

    def snapshot(self) -> Dict[str, Any]:
        """``{"buckets": [(le, cumulative_count), ..., ("+Inf", n)],
        "sum": float, "count": int}`` — one locked copy."""
        with self._lock:
            counts = list(self._counts)
            total, n = self._sum, self._n
        out, cum = [], 0
        for b, c in zip(self.bounds, counts):
            cum += c
            out.append((b, cum))
        return {"buckets": out + [("+Inf", n)], "sum": total, "count": n}


class _PhaseScope:
    """``with tele.phase(name):`` — the open phase gives way to ``name``
    and resumes (as a new span of its own name) when the body ends: nested
    in the code, flat in the trace."""

    __slots__ = ("tele", "name", "prev")

    def __init__(self, tele: "StepTelemetry", name: str):
        self.tele, self.name, self.prev = tele, name, None

    def __enter__(self) -> "_PhaseScope":
        self.prev = self.tele.phase_enter(self.name)
        return self

    def __exit__(self, *exc) -> bool:
        self.tele.phase_enter(self.prev)
        return False


class _Ingress:
    """One request between the ASGI app's first stamp and its
    ``EngineLoop.submit``: what ``ingress_inflight`` counts while ``open``."""

    __slots__ = ("t_begin", "open", "token")

    def __init__(self, t_begin: float):
        self.t_begin, self.open, self.token = t_begin, True, None


#: the request's ``_Ingress``, set by the serving layer's ``_InferScope`` and
#: carried onto the lane thread by its contextvars copy (as the deadline and
#: the QoS tag are), where ``EngineLoop.submit`` closes it
_ingress: "contextvars.ContextVar[Optional[_Ingress]]" = (
    contextvars.ContextVar("request_ingress", default=None))


class StreamTrack:
    """One streamed response's way out, from the engine's ``on_token`` to
    the socket: the token queue, the stamps of the event in flight and the
    stream's own tallies. Made by :meth:`StepTelemetry.stream_open`.

    Two threads touch it, no two of them one field at a time. The
    engine-loop thread calls :meth:`put` for each token and
    :meth:`resolved` as it resolves the request's future (an end mark
    behind the last token): an append and a mark on the telemetry's dirty
    list, no lock and no wake-up of their own. As a step leaves
    ``engine.commit`` (and where the loop resolved or cancelled requests)
    :meth:`StepTelemetry.stream_flush` posts ONE ``call_soon_threadsafe``
    for every stream the step touched. The server's event loop runs the
    rest: the response's async generator takes EVERYTHING ``q`` holds when
    its turn comes (:meth:`took` a token), sends it as one event
    (:meth:`hand_on`, :meth:`sent` behind the write, :meth:`wrote` as it
    resumes), waits in :meth:`wait` when ``q`` is empty, and ends in
    :meth:`last` and :meth:`close`. So an event carries one token while
    the stream keeps up and several when it has fallen behind; at most one
    event is in flight. The shared counters and histograms move under the
    telemetry's stream lock, once a written event (``wrote``)."""

    def __init__(self, tele: "StepTelemetry",
                 trace: Optional[Trace] = None):
        self.tele = tele
        self.trace = trace
        # (token, commit stamp); the end mark is (None, resolve stamp). A
        # SimpleQueue: ``put`` never blocks and takes no Python-level lock,
        # and nobody waits in ``get`` (the stream takes with ``get_nowait``)
        self.q: "queue.SimpleQueue[Tuple[Optional[int], float]]" = (
            queue.SimpleQueue())
        self.n_put = 0       # loop thread
        self._dirty = False  # on the telemetry's dirty list, not yet flushed
        # event loop: the loop the stream is drained on (set before its
        # first wait) and the future its turn waits on
        self._loop = None
        self._waiter = None
        self.held = 0        # event loop: taken, in no event yet
        self._t_commit = self._t_taken = 0.0
        self._pending: Optional[Tuple[int, float, float, float]] = None
        self._final = False
        # chunks written since ``wrote`` last looked
        self._t_written = 0.0
        self._w_events = self._w_bytes = 0
        self.n_sent = 0      # from here on: under the stream lock
        self.n_events = 0
        self._deliver_sum = self._deliver_max = 0.0
        self._t_first = 0.0
        self._t_resolved: Optional[float] = None
        self._ended = self._settled = False

    # -- engine-loop thread -------------------------------------------------

    def put(self, tok: int) -> None:
        """The engine's ``on_token``: the token with the stamp of the phase
        boundary that committed it (``engine.commit``'s, which
        ``phase_enter`` read: no clock is read here)."""
        tele = self.tele
        self.q.put((tok, tele.phase_t0))
        self.n_put += 1
        tele.stream_tokens_put += 1
        self._touch()

    def resolved(self, fut=None) -> None:
        """The request's future resolved (its done-callback: the loop thread,
        inside ``loop.resolve`` or a cancel): its row is free and no token
        follows, so the end mark goes behind the last one. From here to the
        last byte the caller is draining."""
        tele = self.tele
        with tele._stream_lock:
            if self._t_resolved is not None:
                return
            self._t_resolved = tele.phase_t0
            if not self._ended:
                tele._stream["draining"] += 1
            self._settle_locked()
        self.q.put((None, tele.phase_t0))
        self._touch()

    def _touch(self) -> None:
        """``q`` got something: the next :meth:`StepTelemetry.stream_flush`
        wakes this stream."""
        if not self._dirty:
            self._dirty = True
            self.tele._stream_dirty.append(self)

    # -- the server's event loop --------------------------------------------

    def wait(self, loop):
        """An awaitable for "``q`` holds something" (``loop``: the running
        event loop, which the flush will wake). Done at once if it already
        does: what was put before ``_loop`` was known woke nobody."""
        self._loop = loop
        w = self._waiter = loop.create_future()
        if not self.q.empty():
            w.set_result(None)
        return w

    def wake(self) -> None:
        """The flush's call on the event loop: the stream's turn."""
        w = self._waiter
        if w is not None and not w.done():
            w.set_result(None)

    def sent(self, n_bytes: int) -> None:
        """``StreamingResponse.on_sent``: the chunk handed on last is on
        the socket. A stamp and two adds: the rest is ``wrote``'s."""
        self._t_written = time.monotonic()
        self._w_events += 1
        self._w_bytes += n_bytes

    def took(self, t_commit: float) -> None:
        """A token left ``q`` (call right behind ``q.get_nowait``)."""
        self._t_commit, self._t_taken = t_commit, time.monotonic()
        self.held += 1

    def hand_on(self, timed: bool = True) -> None:
        """The event about to be yielded carries every token taken since
        the last one, ``held`` of them, with the LAST one's stamps: where
        an event carries several (the stream had fallen behind), its wake
        is the newest token's, its deliver the newest's, and the older
        ones waited longer than it says. ``timed``: not for the tail a
        finished stream flushes (it waited for the future)."""
        self._pending = (self.held, self._t_commit if timed else 0.0,
                         self._t_taken, time.monotonic())
        self.held = 0

    def wrote(self) -> None:
        """Behind the ``yield`` of an event that carried tokens (the drain
        has written it and asked for the next chunk): the ONE locked call
        a written event, which counts its ``held`` tokens and itself
        (``events_sent`` counts the events that carried tokens, so
        ``tokens_sent`` over it is the tokens an event carried: 1.0 while
        every stream keeps up). Chunks that carry none (the preamble, the
        finish event, ``[DONE]``) add their bytes with the next call."""
        if not self._w_events:
            return      # resumed to be closed: nothing was written
        now, n_bytes = self._t_written, self._w_bytes
        self._w_events = self._w_bytes = 0
        pending, self._pending = self._pending, None
        tele = self.tele
        with tele._stream_lock:
            c = tele._stream
            c["bytes_sent"] += n_bytes
            if pending is not None:
                n, t_commit, t_taken, t_handed = pending
                c["events_sent"] += 1
                c["tokens_sent"] += n
                self.n_sent += n
                if t_commit:
                    deliver = now - t_commit
                    tele.stream_wake.observe_locked(t_taken - t_commit)
                    tele.stream_encode.observe_locked(t_handed - t_taken)
                    tele.stream_write.observe_locked(now - t_handed)
                    tele.stream_deliver.observe_locked(deliver)
                    self.n_events += 1
                    self._deliver_sum += deliver
                    self._deliver_max = max(self._deliver_max, deliver)
                    self._t_first = self._t_first or t_commit

    def last(self) -> None:
        """The chunk about to be yielded is the stream's last."""
        self.wrote()
        self._final = True

    def close(self) -> None:
        """The generator is done (its ``finally``): whole if its last chunk
        was written, aborted otherwise (client gone, write failed)."""
        whole = self._final and self._w_events > 0
        now = self._t_written if whole else time.monotonic()
        self.wrote()
        self._end(whole, now)

    def _end(self, whole: bool, now: float) -> None:
        """Once a stream. Whole: what was taken and is in no event (a
        partial character a stop left behind) went with the stream's end;
        aborted: what was put and not sent is dropped once the future has
        resolved too (the engine puts until the cancel lands)."""
        tele = self.tele
        with tele._stream_lock:
            if self._ended:
                return
            self._ended = True
            c = tele._stream
            lag = None
            if whole:
                c["streams_ended"] += 1
                c["tokens_sent"] += self.held
                self.n_sent += self.held
                self.held = 0
                if self._t_resolved is not None:
                    lag = max(0.0, now - self._t_resolved)
                    tele.stream_finish_lag.observe_locked(lag)
            else:
                c["streams_aborted"] += 1
            if self._t_resolved is not None:
                c["draining"] -= 1
            self._settle_locked()
            n_sent, n_events = self.n_sent, self.n_events
        if self.trace is not None:
            attrs: Dict[str, Any] = {"tokens": n_sent, "events": n_events}
            if n_events:
                attrs["deliver_mean_ms"] = round(
                    self._deliver_sum / n_events * 1e3, 3)
                attrs["deliver_max_ms"] = round(self._deliver_max * 1e3, 3)
            if lag is not None:
                attrs["finish_lag_ms"] = round(lag * 1e3, 3)
            if not whole:
                attrs["aborted"] = True
            self.trace.add_span("stream.deliver", self._t_first or now, now,
                                **attrs)

    def _settle_locked(self) -> None:
        """With the stream ended AND the future resolved ``n_put`` is
        final: what was never sent is dropped, and put = sent + dropped."""
        if (self._ended and self._t_resolved is not None
                and not self._settled):
            self._settled = True
            self.tele._stream["tokens_dropped"] += self.n_put - self.n_sent


def _wake_streams(tracks: List[StreamTrack]) -> None:
    """On the event loop, once a flush: every touched stream's turn."""
    for track in tracks:
        track.wake()


class StepTelemetry:
    """One engine's step-loop instruments: cumulative counters, request
    latency histograms, and a bounded ring of per-step records (the flight
    recorder's engine-side feed). All methods are thread-safe; the engine
    loop thread writes, scrape/dump threads read."""

    def __init__(self, total_blocks: int = 0, max_steps: int = 256):
        self._lock = threading.Lock()
        self.total_blocks = total_blocks
        # conformance instruments (optional, attached by the engine at
        # construction): obs.slo.SloEngine, obs.sentinel.PerfSentinel,
        # obs.hbm.HbmLedger. Riding on the telemetry object keeps ONE
        # provider seam (ModelService.engine_telemetry) feeding /stats,
        # /metrics, and the failover controller alike.
        self.slo = None
        self.sentinel = None
        self.hbm = None
        # host KV tier (kvtier.pool.HostKVTier): attached by the engine
        # when SHAI_KVTIER is on; its gauges merge into snapshot() so the
        # admission gate and /stats see host-pool saturation alongside
        # the device KV gauges
        self.kvtier = None
        # network KV transport (kvnet.client.KvNetStats): attached by the
        # serving layer when the pod participates in disaggregated
        # prefill/decode — the shai_kvnet_* families export through the
        # same collector seam
        self.kvnet = None
        # live-migration counters (kvnet.migrate.MigrateStats): attached
        # by the engine unconditionally — the shai_migrate_* families
        # export through the same collector seam (ship/accept/resume all
        # count onto the one object)
        self.migrate = None
        # KV-fabric probe counters (kvnet.directory.KvFabricStats):
        # attached by the engine only when the fabric is armed — the
        # shai_kvfabric_* families export through the same collector
        # seam, and fabric-off pods show no kvfabric section at all
        self.kvfabric = None
        # QoS weighted-fair scheduler (resilience.qos), attached by the
        # engine when SHAI_QOS is on: its pick/aging counters ride the
        # same provider seam into /stats -> "qos"
        self.qos_sched = None
        # the process's collections and stops (obs.stops.ProcessStops):
        # attached by the serving app that started it, so its groups ``gc``
        # and ``stops`` ride the snapshot; an engine with no app has none
        self.stops = None
        # per-tenant attribution (bounded: MAX_TENANT_LABELS + "other"):
        # cumulative request/finish counts, TTFT histograms, and the
        # last-step waiting/running gauges the engine feeds when QoS (or
        # any tenant tag) is live
        self._tenants: Dict[str, Dict[str, float]] = {}
        self._tenant_ttft: Dict[str, BucketHistogram] = {}
        self._steps: deque = deque(maxlen=max_steps)
        self.ttft = BucketHistogram(TTFT_BUCKETS)
        self.tpot = BucketHistogram(TPOT_BUCKETS)
        self.queue_wait = BucketHistogram(QUEUE_WAIT_BUCKETS)
        self.step_gap = BucketHistogram(STEP_GAP_BUCKETS)
        self.intake_wait = BucketHistogram(INTAKE_WAIT_BUCKETS)
        # a token's way out and a request's way in, counted where they
        # happen, off the loop thread (StreamTrack, ingress_*): one lock of
        # their own, so the server's event loop never holds the lock the
        # loop thread takes at every phase boundary. ``draining`` (future
        # resolved, last byte not written) and ``ingress_inflight`` (begun,
        # not yet submitted) are gauges, a locked step a REQUEST.
        self._stream_lock = threading.Lock()
        self.stream_wake = BucketHistogram(STREAM_BUCKETS, self._stream_lock)
        self.stream_encode = BucketHistogram(STREAM_BUCKETS,
                                             self._stream_lock)
        self.stream_write = BucketHistogram(STREAM_BUCKETS, self._stream_lock)
        self.stream_deliver = BucketHistogram(STREAM_BUCKETS,
                                              self._stream_lock)
        self.stream_finish_lag = BucketHistogram(STREAM_BUCKETS,
                                                 self._stream_lock)
        self.ingress = BucketHistogram(INGRESS_BUCKETS, self._stream_lock)
        self._stream: Dict[str, int] = dict.fromkeys(
            ("tokens_sent", "tokens_dropped", "events_sent", "bytes_sent",
             "streams_started", "streams_ended", "streams_aborted",
             "draining", "ingress_inflight"), 0)
        # tokens handed to streams: written by the ONE thread that steps
        # the engine (``on_token`` runs there), a plain add with no lock
        self.stream_tokens_put = 0
        # the streams whose queue got something since the last flush (a
        # deque: appended and popped from any thread with no lock)
        self._stream_dirty: deque = deque()
        # the engine's own output, whatever path delivers it
        self.tokens_committed = 0
        # where the engine-loop thread's time goes (PHASES): cumulative
        # seconds by phase, the open phase with its start and annotation,
        # and the running step's share by ring-record field. One thread,
        # the one that steps the engine, enters phases; any thread reads.
        # ``phase_cpu_s``: the same thread's CPU seconds by phase, over
        # the phases of one step in ``CPU_SAMPLE_EVERY``
        # (``time.thread_time()`` at their boundaries; closed phases only:
        # another thread cannot read this one's CPU clock), and
        # ``phase_cpu_wall_s``: the wall seconds of those same phases
        self.phase_s: Dict[str, float] = dict.fromkeys(PHASES, 0.0)
        self.phase_cpu_s: Dict[str, float] = dict.fromkeys(PHASES, 0.0)
        self.phase_cpu_wall_s: Dict[str, float] = dict.fromkeys(PHASES, 0.0)
        self.phase_t0 = 0.0          # monotonic start of the open phase
        self._cpu_sampled = True     # until the first step says otherwise
        self._phase_cpu0: Optional[float] = None   # the CPU clock then
        self._phase: Optional[str] = None
        self._phase_ann = None
        self._step_ms: Dict[str, float] = {}
        self._step_no = 0
        self._waiting_peak = 0
        # stalled steps: the ring's median duration (taken once a ring's
        # length of judged steps: a sort of 256 a step is 20 us the loop
        # thread does not have; none until the ring has filled once), the
        # judged steps since, and what was counted
        self._stall_median_s: Optional[float] = None
        self._stall_every = max(1, max_steps)
        self._stall_judged = 0
        self._stall_steps = 0
        self._stall_excess_s = 0.0
        self._stall_by_phase: Dict[str, int] = {}
        # cumulative counters
        self.steps = 0
        self.preemptions = 0
        self.recompiles = 0          # post-warm (bucket-miss) executables
        self.requests_finished = 0
        # async-decode pipeline flushes: the in-flight lookahead step was
        # retired early because an event changed batch composition or
        # control flow (cancel/timeout/join/finish/spec/preempt/idle) —
        # each one is a serialization point the steady path avoids
        self.pipeline_flushes = 0
        self._flush_reasons: Dict[str, int] = {}
        self._step_flushes: List[str] = []   # the open step's, by reason
        # event steps whose prefill or continuation program was queued
        # while a decode step was still in flight (by the step's reason,
        # ``admission`` or ``chunking``): the flush behind it read that
        # step while the program ran, and drained nothing
        self.events_dispatched_ahead = 0
        self._ahead_reasons: Dict[str, int] = {}
        # event steps whose decode dispatch met admissions' first tokens
        # still on the device, and those of them whose decode step went
        # out BEFORE the tokens were read (fed on the device). Equal where
        # nothing reads a pending token on the way (no drafter, no
        # preemption inside the grow)
        self.first_token_events = 0
        self.first_token_events_fed = 0
        # pad-waste accounting: per dispatch, how many token slots the
        # executable walked for REAL context vs shape padding (batch pad
        # rows + the paged kernel's tiles beyond each row's live tokens +
        # prefill bucket tails). A kernel or a ladder that walks more
        # than the rows hold shows up as pad_fraction on a live pod.
        self.pad_tokens = 0
        self.real_tokens = 0
        # per-phase split of the same accounting (prefill admission /
        # chunk continuation / decode / verify): where the pad waste
        # lives decides WHICH ladder to collapse
        self.pad_by_phase: Dict[str, int] = {}
        self.real_by_phase: Dict[str, int] = {}
        # the same accounting's count of dispatches: work done has a
        # number of programs, not only of tokens
        self.dispatches_by_phase: Dict[str, int] = {}
        # host-to-device arrays put for decode and verify dispatches
        # (a table refresh counts one, a new composition each of its
        # arrays): over ``steps`` it says whether a steady step hands the
        # device only what changed
        self.decode_input_uploads = 0
        # what routing did, in the decode dispatches of a model with expert
        # layers (the device counts inside the step it already runs; the
        # counts ride back behind the sampled tokens, in the same read):
        # expert-layer steps, assignments (real rows x experts per token),
        # distinct experts touched and the largest load on one expert, the
        # last two summed over expert layers and steps. None = no experts:
        # the snapshot then has no ``moe`` entry.
        self.moe: Optional[Dict[str, int]] = None
        # what the window bought in the same dispatches of a model with
        # window layers: token slots the paged kernel walked and skipped
        # and the keys its queries could see, in window layers;
        # ``pool_tokens_dead``: tokens x window layers the ONE block table
        # still holds below the rows' windows (gauge, last dispatch), with
        # its sum over dispatches beside the sum of all tokens x layers
        # held — what a per-kind allocator would have to free
        self.window: Optional[Dict[str, int]] = None
        # what the absorbed kernel read in the decode dispatches of a model
        # with a latent cache (counted on the device, like ``moe``):
        # latent-layer steps, and the cache rows the steps' live rows held,
        # summed over latent layers. None = no latent cache.
        self.mla: Optional[Dict[str, int]] = None
        # what the recurrent layers did, counted on the host where it is
        # known, under the model's recurrent KIND (``kda``: linear
        # attention; ``ssm``: state-space mixers; ``conv``: gated short
        # convolutions): real tokens x recurrent layers through the prefill
        # pass, continuation programs that read a slot's state, live rows x
        # recurrent layers stepped in decode dispatches. None = no layer of
        # that kind.
        for kind in RECURRENT_KINDS:
            setattr(self, kind, None)
        self.warmed_executables = 0  # closed-set size at readiness
        # last-step gauges (scraped between steps)
        self._gauges: Dict[str, float] = {}
        # step-watchdog feed (resilience.drain.StepWatchdog): monotonic
        # stamp of the last COMPLETED step. Initialized at construction so
        # "busy since boot, never stepped" reads as an ever-growing age.
        self._last_step_mono = time.monotonic()

    # -- counter hooks (called from the engine) ----------------------------

    def count_preemption(self) -> None:
        with self._lock:
            self.preemptions += 1

    def count_recompile(self) -> None:
        """One executable built after warm-up."""
        with self._lock:
            self.recompiles += 1

    def count_flush(self, reason: str = "") -> None:
        """One flush of the lookahead. Inside a step it is counted as the
        step is recorded, WITH it (``record_step``), so ``pipeline_flushes``
        and ``steps`` move together: a snapshot taken between a step's
        flush and its record would read one flush more than steps, and a
        window in which every step flushes a share over 100%."""
        with self._lock:
            if self._step_no > self.steps:      # a step is open
                self._step_flushes.append(reason)
                return
            self.pipeline_flushes += 1
            if reason:
                self._flush_reasons[reason] = (
                    self._flush_reasons.get(reason, 0) + 1)

    def count_ahead(self, reason: str) -> None:
        """One event step that dispatched its program behind the decode
        step in flight."""
        with self._lock:
            self.events_dispatched_ahead += 1
            self._ahead_reasons[reason] = (
                self._ahead_reasons.get(reason, 0) + 1)

    def count_first_tokens(self, fed: bool) -> None:
        """One event step whose decode dispatch met first tokens still on
        the device; ``fed``: its decode step went out before their read.
        Both move under one lock, so no snapshot reads one without the
        other."""
        with self._lock:
            self.first_token_events += 1
            self.first_token_events_fed += int(fed)

    # -- phases of the engine-loop thread -----------------------------------

    def phase_enter(self, name: Optional[str]) -> Optional[str]:
        """Close the open phase and open ``name`` (``None``: open nothing)
        at one read of the monotonic clock (in a sampled step, one of the
        thread's CPU clock too). The closed phase's seconds add to
        ``phase_s`` (where both its ends read the CPU clock, its CPU
        seconds to ``phase_cpu_s`` and its seconds to ``phase_cpu_wall_s``)
        and to the running step's ring record;
        the opened one is a ``TraceAnnotation`` through ``obs.trace.annotate``
        (tracing switched off: seconds only), an engine phase's with its
        step's number: the join with the ring. Returns the phase it
        closed."""
        if self._stream_dirty and self._phase == "engine.commit":
            self.stream_flush()   # commit's last act, on its own clock
        now = time.monotonic()
        cpu = time.thread_time() if self._cpu_sampled else None
        if self._phase_ann is not None:
            self._phase_ann.__exit__(None, None, None)
        with self._lock:
            prev = self._phase
            if prev is not None:
                dt = now - self.phase_t0
                self.phase_s[prev] = self.phase_s.get(prev, 0.0) + dt
                if cpu is not None and self._phase_cpu0 is not None:
                    self.phase_cpu_s[prev] = (
                        self.phase_cpu_s.get(prev, 0.0)
                        + cpu - self._phase_cpu0)
                    self.phase_cpu_wall_s[prev] = (
                        self.phase_cpu_wall_s.get(prev, 0.0) + dt)
                field = _STEP_FIELD.get(prev)
                if field is not None:
                    self._step_ms[field] = (self._step_ms.get(field, 0.0)
                                            + dt * 1e3)
                elif prev == "engine.record" and self._steps:
                    # the record was written as this phase opened
                    self._steps[-1]["record_ms"] = round(dt * 1e3, 4)
            self._phase, self.phase_t0 = name, now
            self._phase_cpu0 = cpu
            step = self._step_no
        if name is None:
            self._phase_ann = None
        else:
            self._phase_ann = (annotate(name, step=step)
                               if name.startswith("engine.")
                               else annotate(name))
            self._phase_ann.__enter__()
        return prev

    def phase(self, name: str) -> _PhaseScope:
        """``with`` form of :meth:`phase_enter`: ``name`` interrupts the open
        phase, which resumes when the body ends."""
        return _PhaseScope(self, name)

    def begin_step(self, n_waiting: int) -> Optional[str]:
        """An engine step starts: its phases add to a fresh record,
        ``n_waiting`` (the queue after intake, before admission: the most
        it holds this step) is its ``waiting_peak``, and ``engine.admit``
        opens. Returns the phase that was open (``None``: the caller steps
        the engine with no loop around it, and closes the last phase)."""
        with self._lock:
            self._step_ms = {}
            self._step_no = self.steps + 1
            self._waiting_peak = n_waiting
            self._cpu_sampled = self._step_no % CPU_SAMPLE_EVERY == 0
        return self.phase_enter("engine.admit")

    # -- a token's way out, a request's way in -----------------------------

    def stream_open(self, trace: Optional[Trace] = None) -> StreamTrack:
        """A streamed response starts: its :class:`StreamTrack`, whose
        ``put`` is the request's ``on_token``; ``trace``: the request's,
        which gets the stream's one summary span."""
        with self._stream_lock:
            self._stream["streams_started"] += 1
        return StreamTrack(self, trace)

    def stream_flush(self) -> None:
        """Wake every stream whose queue got something since the last call:
        ONE ``call_soon_threadsafe`` on the server's event loop, whatever
        their number (one a loop, where tests run several). Called by the
        engine-loop thread as a step leaves ``engine.commit`` (from
        ``phase_enter``) and by ``EngineLoop`` where it resolved or
        cancelled requests; a stream that is not being drained yet finds
        its tokens when it first looks."""
        dirty = self._stream_dirty
        by_loop: Dict[Any, List[StreamTrack]] = {}
        while dirty:
            try:
                track = dirty.popleft()
            except IndexError:      # another thread's flush took it
                break
            # cleared BEFORE the wake is posted: a put from here on marks
            # the stream again, and one from before is in ``q`` by the time
            # the woken stream looks
            track._dirty = False
            if track._loop is not None:
                by_loop.setdefault(track._loop, []).append(track)
        for loop, tracks in by_loop.items():
            try:
                loop.call_soon_threadsafe(_wake_streams, tracks)
            except RuntimeError:    # the loop is closed: so are its streams
                pass

    def ingress_begin(self, t_begin: float) -> _Ingress:
        """A request that will reach the engine was begun at ``t_begin``
        (the ASGI app's first stamp) and is on its way in; close it with
        :meth:`ingress_end` in the context that called this."""
        ing = _Ingress(t_begin)
        with self._stream_lock:
            self._stream["ingress_inflight"] += 1
        ing.token = _ingress.set(ing)
        return ing

    def ingress_submitted(self, now: float) -> None:
        """``EngineLoop.submit``, on the caller's thread, at its own stamp:
        the context's request, if it is still on its way in, has arrived."""
        ing = _ingress.get()
        if ing is None:
            return
        with self._stream_lock:
            if not ing.open:
                return
            ing.open = False
            self._stream["ingress_inflight"] -= 1
            self.ingress.observe_locked(max(0.0, now - ing.t_begin))

    def ingress_end(self, ing: _Ingress) -> None:
        """The request's scope ends; one that never submitted (refused on
        the way, or served without the engine) leaves the gauge here."""
        with self._stream_lock:
            if ing.open:
                ing.open = False
                self._stream["ingress_inflight"] -= 1
        _ingress.reset(ing.token)

    def stream_snapshot(self) -> Dict[str, int]:
        """The ``stream`` entry of :meth:`snapshot`: counters, the two
        gauges, and ``backlog`` (put, neither sent nor dropped yet)."""
        with self._stream_lock:
            out = dict(self._stream)
        # read BEHIND the others: a token is put before it is sent or
        # dropped, so the backlog never reads below 0
        out["tokens_put"] = self.stream_tokens_put
        out["backlog"] = (out["tokens_put"] - out["tokens_sent"]
                          - out["tokens_dropped"])
        return out

    # -- per-tenant attribution (multi-tenant QoS) -------------------------

    def _tenant_key(self, tenant: str) -> str:
        """Bounded label for ``tenant`` (callers hold ``_lock``): known
        tenants keep their label, the table admits new ones up to
        MAX_TENANT_LABELS, overflow collapses into "other"."""
        t = tenant or _DEFAULT_TENANT
        # shai-lint: allow(guarded-read) caller-holds-lock helper: every
        # caller enters under `with self._lock`
        if t in self._tenants or len(self._tenants) < MAX_TENANT_LABELS:
            return t
        return _OTHER_TENANT

    def _tenant_ent(self, tenant: str) -> Dict[str, float]:
        key = self._tenant_key(tenant)
        # shai-lint: allow(guarded-read) caller-holds-lock helper:
        # every caller enters under `with self._lock`
        ent = self._tenants.get(key)
        if ent is None:
            # shai-lint: allow(thread) caller-holds-lock helper (above)
            ent = self._tenants[key] = {"requests": 0, "waiting": 0,
                                        "running": 0}
        return ent

    def count_tenant_request(self, tenant: str, priority: str = "") -> None:
        """One request submitted under ``tenant`` (engine ``add_request``);
        ``priority`` additionally buckets the count per class."""
        with self._lock:
            ent = self._tenant_ent(tenant)
            ent["requests"] += 1
            if priority:
                k = f"requests_{priority}"
                ent[k] = ent.get(k, 0) + 1

    def note_tenant_ttft(self, tenant: str, v: float) -> None:
        with self._lock:
            key = self._tenant_key(tenant)
            h = self._tenant_ttft.get(key)
            if h is None:
                h = self._tenant_ttft[key] = BucketHistogram(TTFT_BUCKETS)
        h.observe(v)  # BucketHistogram has its own lock

    def tenant_snapshot(self) -> Dict[str, Dict[str, float]]:
        """Per-tenant cumulative counts + last-step gauges (the ``/stats``
        -> ``qos.tenants`` engine-side payload; the serve layer merges the
        budget ledger's view in on top)."""
        with self._lock:
            out = {t: dict(ent) for t, ent in self._tenants.items()}
            hists = list(self._tenant_ttft.items())
        for t, h in hists:
            if t in out:
                snap = h.snapshot()
                out[t]["ttft_count"] = snap["count"]
                if snap["count"]:
                    out[t]["ttft_mean_ms"] = round(
                        snap["sum"] / snap["count"] * 1e3, 3)
        return out

    def tenant_histograms(self) -> Dict[str, Dict[str, Any]]:
        """tenant -> TTFT histogram snapshot (Prometheus adapter feed for
        the ``shai_tenant_ttft_seconds`` family)."""
        with self._lock:
            hists = list(self._tenant_ttft.items())
        return {t: h.snapshot() for t, h in hists}

    def count_pad(self, real: int, padded: int, phase: str = "") -> None:
        """One dispatch's token-slot accounting: ``real`` context/prompt
        tokens the shapes carried vs ``padded`` slots walked only because
        of bucketing/batch padding. ``phase`` additionally buckets the
        split per dispatch kind (``prefill``/``chunk``/``decode``/
        ``verify``) — the totals stay the single source the pad_fraction
        gauge and the unlabelled counters read."""
        with self._lock:
            self.real_tokens += max(0, real)
            self.pad_tokens += max(0, padded)
            if phase:
                self.real_by_phase[phase] = (
                    self.real_by_phase.get(phase, 0) + max(0, real))
                self.pad_by_phase[phase] = (
                    self.pad_by_phase.get(phase, 0) + max(0, padded))
                self.dispatches_by_phase[phase] = (
                    self.dispatches_by_phase.get(phase, 0) + 1)

    def count_moe(self, layer_steps: int, assignments: int,
                  experts_touched: int, load_max: int,
                  streamed_layer_steps: int = 0) -> None:
        """One decode dispatch's expert layers; ``streamed_layer_steps``:
        those of them whose product took the streamed form (all or none:
        ``ops.moe.expert_form`` of the dispatch's rows)."""
        with self._lock:
            m = self.moe if self.moe is not None else {}
            for key, v in (("layer_steps", layer_steps),
                           ("assignments", assignments),
                           ("experts_touched", experts_touched),
                           ("load_max", load_max),
                           ("streamed_layer_steps", streamed_layer_steps)):
                m[key] = m.get(key, 0) + int(v)
            self.moe = m

    def count_moe_tiled(self, layer_calls: int) -> None:
        """One prefill or continuation dispatch's expert layers whose
        product took the tiled form (all or none: ``ops.moe.expert_form``
        of the program's rows); the engine calls this for a dispatch that
        has them, so the key is absent until one did."""
        with self._lock:
            m = self.moe if self.moe is not None else {}
            m["tiled_layer_calls"] = (m.get("tiled_layer_calls", 0)
                                      + int(layer_calls))
            self.moe = m

    def count_mla(self, layer_steps: int, tokens_visible: int) -> None:
        with self._lock:
            m = self.mla if self.mla is not None else {}
            m["layer_steps"] = m.get("layer_steps", 0) + int(layer_steps)
            m["tokens_visible"] = (m.get("tokens_visible", 0)
                                   + int(tokens_visible))
            self.mla = m

    def count_recurrent(self, kind: str, prefill_tokens: int = 0,
                        chunk_carries: int = 0,
                        rows_stepped: int = 0) -> None:
        """One dispatch of a model with recurrent layers of ``kind``
        (one of ``RECURRENT_KINDS``: the snapshot's entry); a model without
        them counts nothing (every argument 0) and shows no such entry."""
        if not (prefill_tokens or chunk_carries or rows_stepped):
            return
        assert kind in RECURRENT_KINDS, kind
        with self._lock:
            m = getattr(self, kind)
            if m is None:
                m = dict.fromkeys(
                    ("prefill_tokens", "chunk_carries", "rows_stepped"), 0)
            m["prefill_tokens"] += int(prefill_tokens)
            m["chunk_carries"] += int(chunk_carries)
            m["rows_stepped"] += int(rows_stepped)
            setattr(self, kind, m)

    def count_window(self, walked: int, skipped: int, visible: int,
                     dead: int, held: int) -> None:
        with self._lock:
            w = self.window if self.window is not None else {}
            for key, v in (("tokens_walked", walked),
                           ("tokens_skipped", skipped),
                           ("tokens_visible", visible),
                           ("pool_dead_token_steps", dead),
                           ("pool_token_steps", held)):
                w[key] = w.get(key, 0) + int(v)
            w["pool_tokens_dead"] = int(dead)
            self.window = w

    def record_step(self, *, kind: str, duration_s: float, n_running: int,
                    n_waiting: int, n_chunking: int, blocks_free: int,
                    slots_free: int = 0,
                    blocks_evictable: int = 0, finished: int = 0,
                    rollback_tokens: int = 0,
                    spec: Optional[Dict[str, Any]] = None,
                    finished_ids: Sequence[int] = (),
                    tenants: Optional[Dict[str, Sequence[int]]] = None,
                    input_uploads: int = 0,
                    state_slots: Optional[int] = None,
                    tokens: int = 0) -> None:
        """One engine ``step()`` completed; ``kind`` names the decode path
        taken (``"decode"``, ``"spec"``, ``"idle"``). ``finished_ids`` are
        the engine request ids that reached a terminal state this step —
        the join key between ``/debug/flight`` step records and request
        traces (whose root carries ``engine_req_id``). ``state_slots``:
        arena slots held at the step's end, of a model with recurrent
        layers (the record's ``state_slots_live``, ``kda.slots_live`` or
        ``ssm.slots_live``).
        ``tokens``: what the step committed (``tokens_committed``). Every
        record also says where the callers outside the engine stand:
        ``ingress_inflight``, ``streams_draining``, ``stream_backlog``."""
        # single int reads, no lock on the loop thread for them; sent and
        # dropped BEFORE put, so the backlog never reads below 0
        # shai-lint: allow(guarded-read) one-int gauges of a step record
        c = self._stream
        sent_or_dropped = c["tokens_sent"] + c["tokens_dropped"]
        total = self.total_blocks or 1
        used = max(0, total - blocks_free)
        # pressure vs occupancy: evictable prefix-cache blocks are
        # RECLAIMABLE — a warm cache legitimately occupies ~100% of the
        # pool (demoting to the host tier on demand), and pricing that as
        # saturation made every warm pod shed 429s and flip the failover
        # controller. kv_utilization (the admission/overload signal)
        # counts live-held blocks only; kv_occupancy keeps the raw view.
        live = max(0, used - max(0, blocks_evictable))
        rec = {
            "ts": round(time.time(), 4),
            "step": 0,  # filled under the lock below
            "kind": kind,
            "duration_s": round(duration_s, 6),
            "running": n_running,
            "waiting": n_waiting,
            "chunking": n_chunking,
            "finished": finished,
            "kv_blocks_free": blocks_free,
            "kv_blocks_evictable": blocks_evictable,
            "kv_utilization": round(live / total, 4),
            "kv_occupancy": round(used / total, 4),
            "rollback_tokens": rollback_tokens,
            "finished_ids": list(finished_ids),
            "ingress_inflight": c["ingress_inflight"],
            "streams_draining": c["draining"],
            "stream_backlog": self.stream_tokens_put - sent_or_dropped,
        }
        if spec:
            rec["spec"] = dict(spec)
        if state_slots is not None:
            rec["state_slots_live"] = int(state_slots)
        with self._lock:
            for m in (getattr(self, kind) for kind in RECURRENT_KINDS):
                if state_slots is not None and m is not None:
                    m["slots_live"] = int(state_slots)
            self.steps += 1
            self.pipeline_flushes += len(self._step_flushes)
            for reason in filter(None, self._step_flushes):
                self._flush_reasons[reason] = (
                    self._flush_reasons.get(reason, 0) + 1)
            self._step_flushes = []
            self.requests_finished += finished
            self.decode_input_uploads += input_uploads
            self.tokens_committed += tokens
            rec["step"] = self.steps
            for field in _STEP_FIELDS:
                rec[field] = round(self._step_ms.get(field, 0.0), 4)
            rec["record_ms"] = 0.0   # set when ``engine.record`` closes
            rec["waiting_peak"] = self._waiting_peak
            rec["preemptions_total"] = self.preemptions
            rec["recompiles_total"] = self.recompiles
            self._steps.append(rec)
            if kind != "idle":
                self._judge_stall(rec, duration_s)
            self._gauges = {
                "running": float(n_running),
                "waiting": float(n_waiting),
                "chunking": float(n_chunking),
                "slots_free": float(slots_free),
                "kv_utilization": rec["kv_utilization"],
                "kv_occupancy": rec["kv_occupancy"],
                "kv_blocks_free": float(blocks_free),
                "last_step_duration_s": rec["duration_s"],
            }
            if spec and "spec_acceptance_rate" in spec:
                self._gauges["spec_acceptance_rate"] = float(
                    spec["spec_acceptance_rate"])
            if tenants is not None:
                # replace-the-gauge semantics: a tenant absent this step
                # reads 0 queued/running, but keeps its cumulative counts
                for ent in self._tenants.values():
                    ent["waiting"] = ent["running"] = 0
                for t, (n_wait, n_run) in tenants.items():
                    ent = self._tenant_ent(t)
                    ent["waiting"] = int(n_wait)
                    ent["running"] = int(n_run)
            self._last_step_mono = time.monotonic()

    def _judge_stall(self, rec: Dict[str, Any], duration_s: float) -> None:
        """Mark and count ``rec`` (the ring's newest) if it stalled; the
        caller holds ``_lock``. The phase that held it is the record's
        longest field: ``fetch`` says the device or a read, anything else
        the host. No clock is read: the duration and the fields are the
        step's own."""
        median = self._stall_median_s
        if median is not None and duration_s > max(
                STALL_FLOOR_S, STALL_TIMES_MEDIAN * median):
            rec["stalled"] = True
            field = max(_STEP_FIELDS, key=rec.__getitem__)
            phase = field[:-3] if rec[field] > 0 else "other"
            self._stall_steps += 1
            self._stall_excess_s += duration_s - median
            self._stall_by_phase[phase] = (
                self._stall_by_phase.get(phase, 0) + 1)
        self._stall_judged += 1
        if self._stall_judged >= self._stall_every:
            self._stall_judged = 0
            # shai-lint: allow(guarded-read) caller-holds-lock helper
            durations = sorted(r["duration_s"] for r in self._steps
                               if r["kind"] != "idle")
            self._stall_median_s = durations[(len(durations) - 1) // 2]

    # -- readouts ----------------------------------------------------------

    def open_phase(self) -> Optional[str]:
        """The phase the loop thread has open now: one unlocked read, for
        the record of a stop (``obs.stops``)."""
        return self._phase

    def last_step_age_s(self, now: Optional[float] = None) -> float:
        """Seconds since the last completed engine step (since construction
        when no step ran yet) — the watchdog's staleness signal."""
        with self._lock:
            last = self._last_step_mono
        return max(0.0, (now if now is not None else time.monotonic()) - last)

    def step_duration_p99(self) -> float:
        """p99 of the recent step-duration ring (0.0 with no steps) — the
        watchdog's scale for what a 'normal' step costs on this tier."""
        with self._lock:
            durations = sorted(r["duration_s"] for r in self._steps)
        if not durations:
            return 0.0
        return durations[min(len(durations) - 1,
                             int(0.99 * (len(durations) - 1)))]

    def recent_steps(self, n: int = 256) -> List[Dict[str, Any]]:
        with self._lock:
            steps = list(self._steps)
        return steps[-n:]

    def snapshot(self) -> Dict[str, Any]:
        """Flat cumulative snapshot: the JSON-line payload and the source of
        the ``/stats`` + Prometheus gauge exports."""
        with self._lock:
            out: Dict[str, Any] = {
                "steps": self.steps,
                "preemptions": self.preemptions,
                "recompiles": self.recompiles,
                "requests_finished": self.requests_finished,
                "warmed_executables": self.warmed_executables,
                "kv_blocks_total": self.total_blocks,
                "pipeline_flushes": self.pipeline_flushes,
                "events_dispatched_ahead": self.events_dispatched_ahead,
                "first_token_events": self.first_token_events,
                "first_token_events_fed": self.first_token_events_fed,
                "decode_input_uploads": self.decode_input_uploads,
                "tokens_committed": self.tokens_committed,
                "pad_tokens": self.pad_tokens,
                "real_tokens": self.real_tokens,
            }
            walked = self.pad_tokens + self.real_tokens
            out["pad_fraction"] = (round(self.pad_tokens / walked, 4)
                                   if walked else 0.0)
            # per-phase split (prefill/chunk/decode/verify) — nested, so
            # flat-numeric consumers (publish_engine) skip it untouched
            out["pad_by_phase"] = {
                p: {"real": self.real_by_phase.get(p, 0),
                    "pad": self.pad_by_phase.get(p, 0)}
                for p in set(self.real_by_phase) | set(self.pad_by_phase)}
            out["dispatches_by_phase"] = dict(self.dispatches_by_phase)
            out["flush_by_reason"] = dict(self._flush_reasons)
            out["ahead_by_reason"] = dict(self._ahead_reasons)
            if self.moe is not None:
                out["moe"] = dict(self.moe)
            if self.window is not None:
                out["window"] = dict(self.window)
            if self.mla is not None:
                out["mla"] = dict(self.mla)
            for kind in RECURRENT_KINDS:
                if getattr(self, kind) is not None:
                    out[kind] = dict(getattr(self, kind))
            # the open phase's seconds so far included: two readings
            # differ by the time between them, whatever each caught open
            out["phase_s"] = dict(self.phase_s)
            if self._phase is not None:
                out["phase_s"][self._phase] = (
                    out["phase_s"].get(self._phase, 0.0)
                    + max(0.0, time.monotonic() - self.phase_t0))
            out["phase_cpu_s"] = dict(self.phase_cpu_s)
            out["phase_cpu_wall_s"] = dict(self.phase_cpu_wall_s)
            out.update(self._gauges)
            out["stall"] = {"steps": self._stall_steps,
                            "excess_s": self._stall_excess_s,
                            "steps_by_phase": dict(self._stall_by_phase)}
        out["stream"] = self.stream_snapshot()
        if self.stops is not None:
            out.update(self.stops.snapshot())   # ``gc`` and ``stops``
        kvt = self.kvtier
        if kvt is not None:
            # host-tier saturation + hit rate travel with the engine
            # snapshot: the admission gate prices host_kv_utilization into
            # shed decisions, and /stats consumers read it here
            try:
                ksnap = kvt.snapshot()
            except Exception:
                ksnap = {}
            out["host_kv_utilization"] = ksnap.get("utilization", 0.0)
            out["host_kv_used_bytes"] = ksnap.get("used_bytes", 0.0)
            out["host_kv_hit_rate"] = ksnap.get("hit_rate", 0.0)
        for name, h in (("ttft", self.ttft), ("tpot", self.tpot),
                        ("queue_wait", self.queue_wait),
                        ("step_gap", self.step_gap),
                        ("intake_wait", self.intake_wait)):
            out[f"{name}_count"] = h.count
        return out

    def histograms(self) -> Dict[str, Dict[str, Any]]:
        """Named histogram snapshots for the Prometheus adapter."""
        return {"ttft_seconds": self.ttft.snapshot(),
                "tpot_seconds": self.tpot.snapshot(),
                "queue_wait_seconds": self.queue_wait.snapshot(),
                "step_gap_seconds": self.step_gap.snapshot(),
                "intake_wait_seconds": self.intake_wait.snapshot(),
                "stream_wake_seconds": self.stream_wake.snapshot(),
                "stream_encode_seconds": self.stream_encode.snapshot(),
                "stream_write_seconds": self.stream_write.snapshot(),
                "stream_deliver_seconds": self.stream_deliver.snapshot(),
                "stream_finish_lag_seconds":
                    self.stream_finish_lag.snapshot(),
                "ingress_seconds": self.ingress.snapshot()}
