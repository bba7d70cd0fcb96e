"""The single serving runtime: one app factory for every model.

The reference copy-pastes ~200-line FastAPI servers per model
(``run-{sd,bert,vit,llama,yolo}.py``, ``*_model_api.py``; SURVEY.md §2.2).
Here the shared surface lives once, and a model contributes only a
:class:`ModelService` (load + warmup + infer + extra routes).

Uniform HTTP surface (reference parity, ``app/run-sd.py:148-203``):

- ``GET  /``                      self-describing config (redacted)
- ``GET  /health``                liveness
- ``GET  /readiness``             readiness — 503 until loaded + warm
- ``POST /benchmark``             ``{"n_runs": N}`` → percentile report
- ``GET  /load/{n}/infer/{m}``    benchmark + metric publication
- ``GET  /metrics``               Prometheus text (the KEDA signal)
- task routes from the service (``/genimage``, ``/generate``, ``/predict``…)

Model calls run on a single-worker executor so the event loop keeps serving
probes while a denoise loop holds the chip; device access is serialized,
matching one-model-per-pod semantics (one deployment unit == one model
replica, reference ``README.md:158-159``).
"""

from __future__ import annotations

import asyncio
import concurrent.futures
import contextvars
import logging
import threading
import time
from typing import Any, Callable, Dict, List, Optional, Tuple

from ..obs import FlightRecorder
from ..obs import stops as obs_stops
from ..obs import trace as obs_trace
from ..resilience import deadline as rz_deadline
from ..resilience import faults as rz_faults
from ..resilience import qos as rz_qos
from ..resilience.admission import AdmissionGate
from ..resilience.drain import DrainController
from ..utils.env import ServeConfig
from .asgi import App, HTTPError, Request, Response
from .latency import LatencyCollector, run_benchmark
from .metrics import MetricsPublisher

log = logging.getLogger(__name__)


class ModelService:
    """One model behind the uniform runtime. Subclasses implement the hooks.

    Lifecycle: ``load()`` (build params + jitted fns, pull artifacts) →
    ``warmup()`` (one synthetic inference per compiled shape, the readiness
    gate; reference ``app/run-sd.py:144-146``) → ``infer(payload)`` per
    request.
    """

    #: task name for the self-describing root endpoint
    task: str = "generic"
    #: route the default POST handler mounts at
    infer_route: str = "/infer"
    #: how many requests may be in ``infer`` simultaneously. 1 = the model
    #: call itself owns the device (default). Engine-backed services raise
    #: this to their slot count — infer() then only enqueues into the engine
    #: loop (which owns the device), so concurrent requests batch together.
    concurrency: int = 1
    #: multi-host serving contract (serve.multihost): True only when EVERY
    #: path to the device — warmup, infer, extra routes — goes through the
    #: methods named in ``mirror_methods``, so followers can mirror each
    #: call and join its collectives. A service with an unmirrored device
    #: entry would wedge the slice; serve_multihost refuses it.
    supports_multihost: bool = False
    mirror_methods: Tuple[str, ...] = ("infer",)
    #: seconds from boot to ready by phase, written once by a unit that
    #: keeps them (the ``vllm`` unit); ``/stats`` shows it as ``startup``
    startup: Dict[str, float] = {}

    def __init__(self, cfg: ServeConfig):
        self.cfg = cfg

    def load(self) -> None:  # pragma: no cover - interface
        raise NotImplementedError

    def warmup(self) -> None:
        """One synthetic end-to-end inference; override for model specifics."""
        self.infer(self.example_payload())

    def example_payload(self) -> Dict[str, Any]:
        """Payload used by warmup and the benchmark endpoints."""
        return {}

    def infer(self, payload: Dict[str, Any]) -> Dict[str, Any]:  # pragma: no cover
        raise NotImplementedError

    def extra_routes(self) -> List[Tuple[str, Tuple[str, ...], Callable]]:
        """Additional (pattern, methods, handler(request)) routes."""
        return []

    def ready_error(self) -> Optional[str]:
        """Post-warm liveness: non-None fails /readiness with the reason.

        Engine-backed services report a dead engine loop here so the LB
        drains the pod instead of routing into guaranteed 500s.
        """
        return None

    def liveness_error(self) -> Optional[str]:
        """Non-None fails ``/health`` (the LIVENESS probe) so Kubernetes
        restarts the pod. Reserved for wedged-beyond-recovery states only —
        engine-backed services report the step watchdog here (a stuck
        dispatch: work pending, no step completing). Readiness-grade
        trouble belongs in :meth:`ready_error`, which merely drains."""
        return None

    def drain(self, budget_s: float) -> None:
        """Finish in-flight work within ``budget_s`` seconds and stop
        accepting more (SIGTERM path). Engine-backed services drain their
        engine loop here; the default is a no-op (plain services have no
        queue beyond the in-flight requests the app already waits on)."""
        return None

    def extra_stats(self) -> Dict[str, float]:
        """Numeric service-level gauges, merged into ``/stats`` and exported
        as ``shai_service_<key>`` Prometheus gauges on ``/metrics`` (so the
        control plane can scale on queue depth or pool pressure, not just
        the request counter). Engine-backed services report queue/slot/block
        occupancy here."""
        return {}

    def affinity_digests(self) -> Optional[List[str]]:
        """Recently served prompt-affinity digests (``kvtier.affinity``),
        advertised under ``/stats`` → ``kvtier.affinity`` so the cova
        orchestrator can route a repeated prompt to the pod whose prefix
        cache / host tier is already warm. None = no advertisement
        (services without an engine or without prefix caching)."""
        return None

    #: disaggregated serving role (kvnet): advertised on ``/stats`` so
    #: cova can route prefill work to prefill pods and hand warm KV to
    #: decode pods; engine-backed services set it from
    #: ``kvnet.resolve_role`` (SHAI_ROLE / EngineConfig.role)
    role: str = "both"

    def kv_tier(self):
        """The host KV block pool (``kvtier.pool.HostKVTier``) backing the
        ``GET /kv/blocks`` transport endpoint, or None when this pod has
        no tier (the route then 404s — peers count a fallback and
        recompute)."""
        return None

    def kvnet_stats(self):
        """The pod's :class:`~..kvnet.client.KvNetStats` counters
        (``shai_kvnet_*``), shared by the serve side (``/kv/blocks``) and
        the fetch side (the decode-role handoff pull); None on pods
        without a tier — the families then never export."""
        return None

    # -- KV fabric (kvnet.directory) ---------------------------------------

    def affinity_heads(self) -> Optional[Dict[str, int]]:
        """Bounded affinity-digest -> chain-head map (``/stats`` →
        ``kvtier.aff_heads``): lets the text-only cova router resolve a
        prompt to the content-addressed head its fleet directory is
        keyed by. None = no fabric participation."""
        return None

    def fabric_pull(self, source: str, head: int) -> Optional[int]:
        """Background replication pull (``POST /kv/pull``): resolve the
        run's hashes via ``source``'s ``/kv/digests?head=`` and fetch it
        into the local tier — the hot-prefix replication path, reusing
        the migrate/warm-pull transport. Returns blocks landed, or None
        when this pod has no fabric (the route 404s and cova tries
        another under-warmed pod next cycle)."""
        return None

    # -- live migration (kvnet.migrate) ------------------------------------

    def wants_migration(self) -> bool:
        """True when the drain should run a migrate phase before the
        budget expires (engine-backed services with migration armed —
        ``SHAI_MIGRATE`` / a configured peer). Default False: plain
        services keep the legacy wait-then-stop drain exactly."""
        return False

    def migrate_inflight(self) -> int:
        """Ship every in-flight request that survived the drain's
        natural-completion window to a healthy peer (the engine snapshots
        each sequence; the waiters ship the manifests and return/stream
        ``migrated`` handoffs). Returns how many requests entered
        migration; 0 on services without an engine."""
        return 0

    def accept_migration(self, manifest, entries):
        """Accept one MIGRATE envelope (``POST /kv/migrate``): restore the
        KV run into the local tier and bank the manifest for its replay.
        Returns the ack dict, or None when this pod cannot accept
        migrations (the route then 404s and the shipper degrades to the
        cold-replay rung). Raises ``kvnet.migrate.MigrateBusy`` when the
        inbox is saturated (the route answers 429 + Retry-After and the
        shipper tries another peer)."""
        return None

    def migrate_busy(self):
        """Retry-After seconds when the migration inbox is saturated —
        the route 429s BEFORE reading the (potentially tens-of-MB)
        envelope body; None = accepting. Default None: services without
        an inbox never push back."""
        return None

    def pending_handoff(self) -> bool:
        """True while this pod still holds banked KV a peer may want to
        pull (``GET /kv/blocks``). The drain holds the server open —
        probe-class GET routes keep serving — until the budget expires
        while this is true: a prefill pod exiting the moment its own
        in-flight count hits zero would strand every handoff run its
        tier banked (the PR-15 drain bugfix)."""
        return False

    def spec_counters(self) -> Optional[Dict[str, int]]:
        """Cumulative speculative-decoding counters
        (``{"drafted", "accepted", "committed"}``) for
        :meth:`MetricsPublisher.publish_spec`, or None when the service has
        no speculative engine. The request path forwards these after each
        served inference so acceptance rate reaches the autoscaling plane."""
        return None

    def engine_telemetry(self):
        """The engine's ``obs.steploop.StepTelemetry`` (None for services
        without an engine). Resolved lazily — the app factory registers the
        Prometheus collector before ``load()`` built the engine — and read
        at every scrape, ``/stats`` call, and ``/debug/flight`` dump."""
        return None

    def step_records(self, n: int = 256) -> List[Dict[str, Any]]:
        """The last ``n`` engine step records for the flight recorder."""
        tele = self.engine_telemetry()
        return tele.recent_steps(n) if tele is not None else []

    def export_artifacts(self, artifact_root: str) -> int:
        """Export portable AOT artifacts (StableHLO via ``core.aot.AotCache``)
        under the artifact root; returns how many were written.

        ``compilectl`` calls this after warmup — the distributable analog of
        the reference pushing per-rank NEFFs to the hub
        (``app/compile-sd2.py:18-20``). Services that only rely on the
        persistent XLA cache return 0.
        """
        return 0


_SERVE_UI_HTML = """<!doctype html><meta charset="utf-8">
<title>%(app)s — %(task)s</title>
<style>body{font-family:sans-serif;max-width:52rem;margin:2rem auto}
textarea{width:100%%;font-family:monospace}pre{background:#f4f4f4;
padding:1rem;overflow:auto}img{max-width:100%%;margin-top:1rem}</style>
<h1>%(app)s <small>(%(task)s)</small></h1>
<p>POST payload for <code>%(route)s</code>:</p>
<textarea id=payload rows=6>%(example)s</textarea>
<p><button onclick="run()">run</button>
<a href="/stats">stats</a> · <a href="/metrics">metrics</a> ·
<a href="/">config</a></p>
<pre id=out></pre><div id=img></div>
<script>
async function run(){
  out.textContent = '...'; img.innerHTML = '';
  const r = await fetch('%(route)s',
    {method:'POST', body: payload.value});
  const body = await r.json();
  if (body.image_b64 && body.image_b64.length > 64) {
    img.innerHTML = '<img src="data:image/png;base64,' + body.image_b64 + '">';
    body.image_b64 = '(' + body.image_b64.length + ' b64 chars, shown below)';
  }
  out.textContent = JSON.stringify(body, null, 1);
}
</script>"""


def create_app(
    cfg: ServeConfig,
    service: ModelService,
    publisher: Optional[MetricsPublisher] = None,
) -> App:
    app = App(title=cfg.app)
    collector = LatencyCollector()
    pub = publisher or MetricsPublisher(cfg.app, cfg.nodepool, cfg.pod_name)
    state = {"loaded": False, "warm": False, "load_error": None,
             "inflight": 0, "lane_pending": 0}
    inflight_lock = threading.Lock()
    # request-lifecycle hardening (resilience): bounded admission in front
    # of the model lane + the SIGTERM drain flag. One threshold owner: the
    # gate prices saturation with the failover controller's numbers, so
    # pod-level 429s and fleet-level failover describe the same line.
    from ..orchestrate.capacity_checker import OverloadThresholds

    # multi-tenant QoS (resilience.qos): the tenant budget ledger rides
    # the admission gate — an over-budget tenant sheds with a Retry-After
    # derived from its token-bucket refill deficit while other tenants
    # keep serving; SHAI_TENANT_MAX_INFLIGHT optionally caps one tenant's
    # concurrency inside its budget
    from ..obs.util import env_int as _env_int

    # request reliability (resilience.idempotency): the bounded per-pod
    # completion cache keyed duplicates replay from. Consulted ONLY for
    # requests carrying X-SHAI-Idempotency-Key — keyless traffic never
    # touches it (the strict no-op gate), and non-idempotent replay stays
    # forbidden without a key (the PR-3 contract).
    from ..obs.util import env_float as _env_float
    from ..resilience import idempotency as rz_idemp

    idem = rz_idemp.IdempotencyCache(
        max_entries=_env_int("SHAI_IDEMP_CACHE", 1024),
        ttl_s=_env_float("SHAI_IDEMP_TTL_S", 600.0))

    ledger = rz_qos.TenantLedger.from_env()
    gate = AdmissionGate(
        OverloadThresholds(max_queue_depth=cfg.admit_max_queue,
                           max_kv_utilization=cfg.admit_max_kv),
        max_inflight=cfg.max_inflight,
        ledger=ledger,
        tenant_max_inflight=_env_int("SHAI_TENANT_MAX_INFLIGHT", 0))
    drainer = DrainController(budget_s=cfg.drain_budget_s)
    # flight recorder: every completed request's span timeline rings here
    # (the asgi layer closes each trace and sinks it), joined at dump time
    # by the engine's step records — GET /debug/flight
    flight = FlightRecorder()
    app.trace_sink = flight.record_request
    # engine telemetry → /metrics: TTFT/TPOT/queue-wait histograms + step
    # gauges/counters, resolved lazily at scrape time
    pub.attach_engine_telemetry(service.engine_telemetry)
    pub.attach_idempotency(lambda: idem)
    # when the process did not run (obs.stops): started with the app and
    # stopped with it, below; a stop's record names the phase the engine
    # loop had open, through the same lazy seam the telemetry comes by
    proc_stops = obs_stops.PROCESS

    def _loop_phase() -> Optional[str]:
        tele = service.engine_telemetry()
        return None if tele is None else tele.open_phase()
    # the model lane: probes never queue behind it. Width 1 serializes device
    # access; engine-backed services widen it (their infer only enqueues).
    lane = concurrent.futures.ThreadPoolExecutor(
        max_workers=max(1, service.concurrency), thread_name_prefix="model")

    app.state.update(cfg=cfg, service=service, collector=collector, publisher=pub,
                     status=state, flight=flight, gate=gate, drainer=drainer,
                     ledger=ledger, idem=idem)
    # lifecycle probes and scrape surfaces must not ring the flight
    # recorder; /kv/blocks is probe-class too — a decode fleet pulling KV
    # runs would otherwise evict real request timelines from the ring
    app.trace_exclude |= {"/health/ready", "/debug/faults",
                          "/debug/conformance", "/profile", "/kv/blocks",
                          "/kv/migrate", "/kv/digests", "/kv/pull",
                          "/kv/protect", "/trace/{trace_id}"}

    def _do_load_and_warm():
        t0 = time.perf_counter()
        try:
            service.load()
            tele = service.engine_telemetry()
            if tele is not None:
                # /stats engine.gc|stops, the shai_process_* families
                tele.stops = proc_stops
            state["loaded"] = True
            log.info("%s: model loaded in %.1fs", cfg.app, time.perf_counter() - t0)
            if cfg.warmup:
                t1 = time.perf_counter()
                service.warmup()
                log.info("%s: warmup done in %.1fs", cfg.app, time.perf_counter() - t1)
            state["warm"] = True
        except Exception as e:
            # pod stays alive but never ready — the reference's fail-fast
            # startup self-test semantics (SURVEY.md §4.1) without a crash loop
            state["load_error"] = f"{type(e).__name__}: {e}"
            log.exception("%s: startup failed", cfg.app)

    @app.startup
    def _kick_off_load():
        # Loading runs on the model lane, NOT the event loop: the listen
        # socket binds immediately and /health + /readiness answer during the
        # multi-minute cold compile (/readiness returns 503 "loading").
        # The instrument first: the warm-up's collections are counted.
        proc_stops.loop_phase = _loop_phase
        proc_stops.start()
        state["load_future"] = lane.submit(_do_load_and_warm)

    @app.shutdown
    def _stop_instruments():
        proc_stops.stop()
        if proc_stops.loop_phase is _loop_phase:
            # the process's instrument outlives the app: it must not keep
            # the app's service alive, nor ask a dead engine for its phase
            proc_stops.loop_phase = None

    async def _run_model(fn: Callable, *args):
        loop = asyncio.get_running_loop()
        # run under a COPY of the caller's context: run_in_executor does not
        # propagate contextvars, and the request trace must follow the model
        # call onto the lane thread so spans opened there nest correctly
        ctx = contextvars.copy_context()
        return await loop.run_in_executor(lane, lambda: ctx.run(fn, *args))

    def _require_ready():
        if state["load_error"]:
            raise HTTPError(500, f"model failed to load: {state['load_error']}")
        if not (state["loaded"] and state["warm"]):
            raise HTTPError(503, "model not ready")
        err = service.ready_error()
        if err:
            raise HTTPError(503, f"model unhealthy: {err}")

    # -- request lifecycle (resilience) ------------------------------------

    def _engine_snapshot() -> Optional[Dict[str, Any]]:
        try:
            tele = service.engine_telemetry()
            return None if tele is None else tele.snapshot()
        except Exception:
            return None

    def _inflight_counts() -> Tuple[int, int]:
        """One locked read of (inflight, lane_pending) — the lock is
        RELEASED before the gate/ledger run so the in-flight counters
        never nest with another lock (shai-race lock-order contract)."""
        with inflight_lock:
            return state["inflight"], state["lane_pending"]

    def _admit(tenant: str = ""):
        """Bounded admission: shed (429/503 + Retry-After) BEFORE the
        request parks a lane thread or enters the engine queue. ``tenant``
        is the ledger-bounded label — per-tenant budgets/caps shed here
        with a budget-derived Retry-After, and every shed is attributed
        per tenant on ``shai_shed_total``."""
        inflight, lane_pending = _inflight_counts()
        shed = gate.check(_engine_snapshot(), inflight=inflight,
                          draining=drainer.draining,
                          lane_width=max(1, service.concurrency),
                          lane_pending=lane_pending,
                          tenant=tenant)
        if shed is not None:
            pub.count_shed(shed.reason, tenant)
            raise HTTPError(shed.status, shed.detail, headers=shed.headers)

    def _deadline_of(request: Request) -> Optional[rz_deadline.Deadline]:
        """The request's deadline: header wins, DEADLINE_MS default fills
        in. Expired-on-arrival is a 504 before any model work."""
        try:
            dl = rz_deadline.deadline_from_headers(
                request.headers, default_ms=float(cfg.deadline_ms))
        except ValueError as e:
            raise HTTPError(400, str(e))
        if dl is not None and dl.expired:
            raise HTTPError(504, "deadline exceeded before processing")
        return dl

    class _InferScope:
        """Admission + deadline + QoS + in-flight accounting around one
        request. The deadline and the tenant/priority tag ride contextvars
        so ``_run_model``'s context copy carries them onto the lane thread
        (and into the engine loop)."""

        def __init__(self, request: Request):
            self.request = request
            self._token = None
            self._qos_token = None
            self._handed_off = False
            # the request's way in (``ingress_inflight``, from the ASGI
            # app's first stamp to ``EngineLoop.submit``), on the engine's
            # telemetry where the service has one
            self._tele = self._ingress = None
            # resolved at __enter__: the ledger-bounded tenant label every
            # shed/charge/inflight count for this request attributes to
            self.tenant = ""

        def __enter__(self):
            raw_tenant, priority = rz_qos.qos_from_headers(
                self.request.headers)
            self.tenant = ledger.label_of(raw_tenant)
            _admit(self.tenant)
            dl = _deadline_of(self.request)
            self._token = rz_deadline.set_current_deadline(dl)
            # the engine tag carries the RAW (sanitized) tenant, not the
            # ledger's "default" label: an untagged request must reach
            # the engine untagged so a single-tenant pod keeps its
            # zero-cost FIFO path and exports no tenant families
            self._qos_token = rz_qos.set_current_qos(
                rz_qos.QosTag(tenant=raw_tenant, priority=priority))
            ledger.note_start(self.tenant)
            with inflight_lock:
                state["inflight"] += 1
                state["lane_pending"] += 1
            self._tele = service.engine_telemetry()
            if self._tele is not None:
                self._ingress = self._tele.ingress_begin(
                    self.request.t_begin)
            return dl

        def charge(self, out) -> None:
            """Debit the tenant's token budget with the request's actual
            usage: prompt + generated tokens for engine responses (OpenAI
            ``usage.total_tokens`` or the /generate fields), a floor of 1
            unit for token-less services/streams — so budgets degrade to
            request-rate metering where token counts don't exist."""
            tokens = 1
            if isinstance(out, dict):
                usage = out.get("usage")
                if isinstance(usage, dict) and isinstance(
                        usage.get("total_tokens"), (int, float)):
                    tokens = int(usage["total_tokens"])
                else:
                    try:
                        tokens = (int(out.get("n_tokens") or 0)
                                  + int(out.get("n_prompt") or 0))
                    except (TypeError, ValueError):
                        tokens = 1
            ledger.charge(self.tenant, max(1, tokens))

        def _dec_inflight(self):
            with inflight_lock:
                state["inflight"] -= 1

        def hand_off_inflight(self):
            """Streaming: the request is in flight until its stream DRAINS,
            not until the handler returns the StreamingResponse — defer the
            decrement to the returned callable (idempotent; called from the
            stream iterator's finally, which runs on drain, disconnect
            abort, and generator close alike). Keeps live SSE streams
            visible to MAX_INFLIGHT and the drain's in-flight wait. The
            lane-pending count drops NOW: the submission's lane thread is
            already free and the stream is drained on the event loop (a
            sync iterator's on the stream pool), so an open stream must
            not read as executor queue depth."""
            self._handed_off = True
            with inflight_lock:
                state["lane_pending"] -= 1
            released = {"v": False}
            tenant = self.tenant

            def release():
                if not released["v"]:
                    released["v"] = True
                    self._dec_inflight()
                    # stream drain/abort: the tenant's in-flight slot frees
                    # and its budget is debited the streaming floor (token
                    # counts never reach the app layer mid-SSE)
                    ledger.note_done(tenant)
                    ledger.charge(tenant, 1)

            return release

        def __exit__(self, *exc):
            if not self._handed_off:
                with inflight_lock:
                    state["inflight"] -= 1
                    state["lane_pending"] -= 1
                ledger.note_done(self.tenant)
            if self._ingress is not None:
                self._tele.ingress_end(self._ingress)
            rz_deadline.reset_current_deadline(self._token)
            rz_qos.reset_current_qos(self._qos_token)
            return False

    def _begin_drain(on_done: Optional[Callable[[], None]] = None) -> bool:
        """SIGTERM semantics, callable without a signal (tests, /debug):
        flip readiness, shed new work, let in-flight requests finish up to
        the drain budget, drain the service (engine loop), then ``on_done``
        (the server's shutdown). Idempotent — one drain per process."""
        if not drainer.begin():
            return False
        log.warning("%s: draining (budget %.1fs) — readiness now 503",
                    cfg.app, drainer.budget_s)

        def _work():
            idle = lambda: _inflight_counts()[0] == 0  # noqa: E731
            # migrate phase (kvnet.migrate): give natural completion the
            # budget MINUS a reservation, then ship what's still running
            # to a healthy peer — pod death becomes a latency event for
            # the long tail instead of an error event at the deadline
            if service.wants_migration():
                from ..kvnet.migrate import migrate_reserve_s

                if not drainer.wait(idle, min_remaining=migrate_reserve_s(
                        drainer.budget_s)):
                    try:
                        n = service.migrate_inflight()
                        if n:
                            log.warning("%s: drain migrated %d in-flight "
                                        "request(s) to a peer", cfg.app, n)
                    except Exception:
                        log.exception("drain migrate phase failed — "
                                      "falling back to the budget wait")
            clean = drainer.wait(idle)
            if not clean:
                log.warning("%s: drain budget expired with %d requests "
                            "in flight", cfg.app, _inflight_counts()[0])
            try:
                service.drain(max(0.0, drainer.remaining_s))
            except Exception:
                log.exception("service drain failed")
            # prefill-handoff hold (the PR-15 drain bugfix): a pod whose
            # host tier still banks handoff KV keeps its probe-class GET
            # routes (/kv/blocks) serving until the budget expires, so
            # peers can pull the runs this pod warmed — exiting at
            # inflight==0 stranded them
            try:
                while service.pending_handoff() and drainer.remaining_s > 0:
                    time.sleep(0.05)
            except Exception:
                log.exception("pending-handoff hold failed")
            if on_done is not None:
                on_done()

        threading.Thread(target=_work, daemon=True, name="drain").start()
        return True

    app.state["begin_drain"] = _begin_drain

    # -- uniform surface ---------------------------------------------------
    @app.get("/")
    def root(request: Request):
        from ..core.device import live_backend

        return {
            "app": cfg.app,
            "task": service.task,
            "model_id": cfg.model_id,
            # the REQUESTED tier, and beside it what JAX actually brought up
            "device": cfg.device,
            **live_backend(),
            "endpoints": sorted({r.pattern for r in app.routes}),
            "config": cfg.describe(),
            "served": pub.served,
        }

    @app.get("/health")
    def health(request: Request):
        # LIVENESS: only wedged-beyond-recovery states fail it (the engine
        # step watchdog) — Kubernetes restarts the pod. A draining pod is
        # still live (it is finishing real work).
        err = service.liveness_error()
        if err:
            return Response({"status": "stuck", "error": err}, status=503)
        return {"status": "ok"}

    @app.get("/readiness")
    @app.get("/health/ready")
    def readiness(request: Request):
        if drainer.draining:
            # SIGTERM flips readiness first: the LB stops routing while
            # in-flight requests finish inside the drain budget
            return Response({"status": "draining"}, status=503)
        if state["load_error"]:
            return Response({"status": "failed", "error": state["load_error"]}, status=500)
        if not (state["loaded"] and state["warm"]):
            return Response({"status": "loading"}, status=503)
        err = service.ready_error()
        if err:
            return Response({"status": "unhealthy", "error": err}, status=503)
        return {"status": "ready"}

    async def _idem_replay_or_claim(key: str):
        """Consult the completion cache for a keyed request: a cached
        result (or a joined in-flight one) comes back as the response;
        None means this caller owns the execution. Joiners park on the
        entry's event OFF the event loop — the idempotency lock is HOT
        and the wait is unbounded-ish (the original's own deadline/600s
        backstop bounds it in practice)."""
        inj = rz_faults.get()
        await inj.asleep_at(rz_faults.IDEMP_LOOKUP)
        st, entry = idem.begin(key)
        if st == "new":
            return None
        if st == "done":
            return dict(entry.result, idempotent_replay=True)
        loop = asyncio.get_running_loop()
        woke = await loop.run_in_executor(None, entry.event.wait, 600.0)
        if entry.state == "done" and entry.result is not None:
            return dict(entry.result, idempotent_replay=True)
        if not woke:
            raise HTTPError(
                409, f"duplicate of an in-flight request (key {key!r}) "
                     f"that has not completed; retry later")
        # the original failed — failures are not cached, this duplicate
        # legitimately runs its own attempt
        return None

    @app.post(service.infer_route)
    async def task_infer(request: Request):
        _require_ready()
        # request reliability: keyed duplicates replay/join instead of
        # re-executing — BEFORE admission and _InferScope, so a replay
        # never charges the tenant ledger a second time
        key = request.headers.get(rz_idemp.IDEMP_HEADER, "")
        if key:
            if not rz_idemp.valid_key(key):
                raise HTTPError(400, "bad idempotency key (want "
                                     "[A-Za-z0-9_.:-]{1,128})")
            cached = await _idem_replay_or_claim(key)
            if cached is not None:
                return cached
        payload = request.json()
        if key:
            payload["idem_key"] = key
        t0 = time.perf_counter()
        try:
            scope = _InferScope(request)
            with scope:
                # annotation=False: this span is held across an await on the
                # event loop; the device-trace view comes from the engine's
                # own prefill/decode annotations on the lane thread
                with obs_trace.span("model_infer", annotation=False):
                    out = await _run_model(service.infer, payload)
            scope.charge(out)
        except BaseException:
            if key:
                idem.fail(key)
            raise
        dt = time.perf_counter() - t0
        collector.record(dt)
        pub.publish(dt)
        sc = service.spec_counters()
        if sc is not None:
            pub.publish_spec(**sc)
        tele = service.engine_telemetry()
        if tele is not None:
            pub.publish_engine(tele)
        if isinstance(out, dict):
            out.setdefault("latency_s", round(dt, 4))
        if key and isinstance(out, dict):
            # publish AFTER the latency stamp so a replay is byte-equal
            # to the original response (modulo the replay marker)
            idem.complete(key, out)
        return out

    @app.post("/benchmark")
    async def benchmark(request: Request):
        _require_ready()
        payload = request.json()
        n_runs = int(payload.get("n_runs", cfg.num_of_runs_inf))
        if n_runs < 1 or n_runs > 10_000:
            raise HTTPError(400, "n_runs must be in [1, 10000]")
        example = payload.get("payload") or service.example_payload()
        report = await _run_model(
            lambda: run_benchmark(lambda: service.infer(example), n_runs, collector)
        )
        return {"app": cfg.app, "report": report.to_dict()}

    @app.get("/load/{n_runs:int}/infer/{n_inf:int}")
    async def load_infer(request: Request, n_runs: int, n_inf: int):
        """Reference parity: N benchmark rounds of M inferences each, with
        metric publication per round (reference ``app/run-sd.py:157-175``)."""
        _require_ready()
        if n_runs < 1 or n_inf < 1 or n_runs * n_inf > 100_000:
            raise HTTPError(400, "bad load shape")
        example = service.example_payload()
        reports = []

        def _one_round():
            # per-sample publication keeps the request counter and the
            # latency histogram in lockstep (1 observation per inference)
            return run_benchmark(
                lambda: service.infer(example), n_inf, collector, on_sample=pub.publish
            )

        for _ in range(n_runs):
            rep = await _run_model(_one_round)
            reports.append(rep.to_dict())
        return {"app": cfg.app, "rounds": reports, "served_total": pub.served}

    @app.get("/metrics")
    def metrics(request: Request):
        if pub.registry is None:
            raise HTTPError(404, "prometheus_client not available")
        from prometheus_client import generate_latest

        return Response(generate_latest(pub.registry), media_type="text/plain; version=0.0.4")

    @app.get("/stats")
    def stats(request: Request):
        inflight, lane_pending = _inflight_counts()
        out = {
            "served": pub.served,
            "latency": collector.report(),
            "count": collector.count,
            "inflight": inflight,
            "lane_pending": lane_pending,
            "draining": drainer.draining,
        }
        if gate.shed_total:
            out["shed"] = {"total": gate.shed_total,
                           **gate.shed_by_reason()}
        # request reliability: the completion cache's counters — present
        # only once a keyed request touched it, so keyless pods keep
        # their exact pre-existing /stats shape
        isnap = idem.snapshot()
        if any(isnap.values()):
            out["idempotency"] = isnap
        try:
            svc = service.extra_stats()
        except Exception:
            svc = {}
        if svc:
            out["service"] = svc
        if service.startup:
            out["startup"] = dict(service.startup)
        tele = service.engine_telemetry()
        if tele is not None:
            out["engine"] = tele.snapshot()
            # conformance sections (PR 7): the failover controller reads
            # "slo" (burn-rate breach → latency-driven failover trigger)
            # and cova /fleet aggregates "hbm"/"perf" per backend
            for sec, obj in (("slo", getattr(tele, "slo", None)),
                             ("hbm", getattr(tele, "hbm", None)),
                             ("perf", getattr(tele, "sentinel", None)),
                             ("kvtier", getattr(tele, "kvtier", None)),
                             ("migrate", getattr(tele, "migrate", None)),
                             ("kvfabric", getattr(tele, "kvfabric",
                                                  None))):
                if obj is not None:
                    try:
                        out[sec] = obj.snapshot()
                    except Exception:
                        pass
        # warm-prefix advertisement (kvtier.affinity): cova's prefix-
        # affinity router reads these digests off /fleet — exported even
        # tier-less, the DEVICE prefix cache is warm too
        aff = service.affinity_digests()
        if aff is not None:
            out.setdefault("kvtier", {})["affinity"] = aff
        # KV fabric (kvnet.directory): the host tier's bounded chain-head
        # advertisement plus the affinity-digest -> chain-head map — what
        # cova's fleet directory is built from. Both are O(bounded) reads
        # off incrementally maintained caches, never an entries walk.
        tier = service.kv_tier()
        if tier is not None and hasattr(tier, "advertisement"):
            out.setdefault("kvtier", {})["adverts"] = tier.advertisement()
        heads = service.affinity_heads()
        if heads:
            out.setdefault("kvtier", {})["aff_heads"] = heads
        # fleet autoscaler (PR 19): the controller's latest decision
        # snapshot — counters (shai_scaler_* families), per-pool state,
        # and the control contract it ran under — published through the
        # orchestrate.scaler module seam by an in-process controller
        # (cova-colocated or the sim harness); pods without one simply
        # omit the section
        try:
            from ..orchestrate.scaler import published as _scaler_pub

            sc = _scaler_pub()
            if sc:
                out["scaler"] = sc
        except Exception:
            pass
        # disaggregated serving (kvnet): the pod's role — what cova's
        # disagg router partitions the fleet by — plus the transport
        # counters when the pod participates in the network KV plane
        out["role"] = service.role
        kn = service.kvnet_stats()
        if kn is not None:
            try:
                out["kvnet"] = kn.snapshot()
            except Exception:
                pass
        # multi-tenant QoS: one "qos" section joining the budget ledger's
        # per-tenant usage (requests/tokens/inflight/shed/budget balance)
        # with the engine's per-tenant queue/slot/TTFT view and the
        # weighted-fair scheduler's pick counters — what cova /fleet
        # aggregates fleet-wide per tenant. Engine-side keys are
        # namespaced `engine_*`: the two sources count different things
        # ("requests" admitted at the door vs submitted to the engine —
        # they diverge on n>1 fan-outs) and run different cardinality
        # caps, so a silent same-key merge would clobber one truth with
        # the other
        tenants: Dict[str, Dict[str, Any]] = {}
        for t, ent in ledger.snapshot().items():
            tenants.setdefault(t, {}).update(ent)
        if tele is not None and hasattr(tele, "tenant_snapshot"):
            for t, ent in tele.tenant_snapshot().items():
                tenants.setdefault(t, {}).update(
                    {f"engine_{k}": v for k, v in ent.items()})
        sched = getattr(tele, "qos_sched", None) if tele is not None \
            else None
        if tenants or sched is not None or ledger.metered:
            out["qos"] = {"metered": ledger.metered, "tenants": tenants}
            if sched is not None:
                out["qos"]["scheduler"] = sched.snapshot()
        from ..core.aot import compile_stats

        out["aot"] = compile_stats()
        return out

    @app.get("/kv/blocks")
    async def kv_blocks(request: Request):
        """Network KV transport (kvnet): serve this pod's host-tier blocks
        by chain hash. ``?hashes=`` is a comma-joined list; the response
        is the LEADING contiguous resident run as length-prefixed binary
        frames (``kvnet.frames``) — ``(k, v)`` per block, or the quant
        4-tuple ``(k, v, ks, vs)``, byte-exact. Probe-class route: no
        admission gate (GET), excluded from the flight ring, bounded by
        ``MAX_BLOCKS_PER_REQUEST``; a pod without a tier 404s and the
        peer degrades to recompute. The copy-and-encode runs on the
        DEFAULT executor, not the event loop (a full-cap pull at real
        geometry is tens of MB of tobytes+crc — on the loop it would
        stall /health and /readiness) and not the model lane (a KV pull
        must never queue behind a denoise/decode holding the device)."""
        from ..kvnet import client as kvnet_client
        from ..kvnet import frames as kvnet_frames

        tier = service.kv_tier()
        if tier is None:
            raise HTTPError(404, "no host KV tier on this pod")
        raw = request.query.get("hashes", "")
        try:
            hashes = [int(h) for h in raw.split(",") if h.strip()]
        except ValueError:
            raise HTTPError(400, "hashes must be comma-joined integers")
        if not hashes:
            raise HTTPError(400, "missing hashes")
        if len(hashes) > kvnet_client.MAX_BLOCKS_PER_REQUEST:
            raise HTTPError(
                400, f"at most {kvnet_client.MAX_BLOCKS_PER_REQUEST} "
                     f"hashes per request")

        def _gather() -> Tuple[int, bytes]:
            run = tier.get_run(hashes)
            return len(run), kvnet_frames.encode_frames(run)

        n_run, body = await asyncio.get_running_loop().run_in_executor(
            None, _gather)
        stats = service.kvnet_stats()
        if stats is not None:
            stats.count_served(n_run, len(body))
        return Response(body, media_type="application/octet-stream",
                        headers={"x-shai-kv-blocks": str(n_run)})

    @app.get("/kv/digests")
    def kv_digests(request: Request):
        """KV fabric advertisement (kvnet.directory): this pod's bounded
        chain-head set — ``{"adverts": [{"head", "n", "seq"}, ...]}`` —
        or, with ``?head=``, one advertised run's full hash chain for a
        replication pull. Probe-class: O(bounded) reads off the tier's
        incrementally maintained caches (never an entries walk), served
        inline on the event loop, trace-excluded. A pod without a tier
        404s — a directory poller treats it as advertising nothing."""
        tier = service.kv_tier()
        if tier is None or not hasattr(tier, "advertisement"):
            raise HTTPError(404, "no host KV tier on this pod")
        raw = request.query.get("head", "")
        if raw:
            try:
                head = int(raw)
            except ValueError:
                raise HTTPError(400, "head must be an integer chain hash")
            return {"head": head, "hashes": tier.run_hashes(head)}
        return {"adverts": tier.advertisement()}

    @app.post("/kv/pull")
    async def kv_pull(request: Request):
        """Hot-prefix replication (kvnet.directory): cova asks this pod
        to pull one advertised run from ``source`` into its own tier —
        ``{"source": url, "head": chain_hash}``. Infrastructure route
        (no admission gate; the pull is background warmth, not a
        request), refused while draining, 404 on fabric-off pods so a
        misconfigured cova can never turn a cold pod into a puller. The
        blocking fetch runs on the default executor."""
        _require_ready()
        if drainer.draining:
            raise HTTPError(503, "pod is draining; pick another peer",
                            headers={"retry-after": "1"})
        body = request.json()
        try:
            source = str(body["source"])
            head = int(body["head"])
        except (ValueError, TypeError, KeyError):
            raise HTTPError(400, "need {source: url, head: chain_hash}")
        n = await asyncio.get_running_loop().run_in_executor(
            None, service.fabric_pull, source, head)
        if n is None:
            raise HTTPError(404, "no KV fabric on this pod")
        return {"fetched": int(n)}

    @app.post("/kv/protect")
    async def kv_protect(request: Request):
        """Last-holder eviction deferral (kvnet.directory): cova marks
        the runs this pod is the fleet's ONLY advertised holder of —
        ``{"heads": [chain_hash, ...], "ttl_s": s}`` — so LRU eviction
        skips them for one directory cycle and a probe in flight never
        races the fleet's last copy out of existence. Bounded, advisory
        (capacity still wins), 404 without a tier."""
        tier = service.kv_tier()
        if tier is None or not hasattr(tier, "protect"):
            raise HTTPError(404, "no host KV tier on this pod")
        body = request.json()
        try:
            heads = [int(h) for h in body.get("heads", [])]
            ttl_s = float(body.get("ttl_s", 5.0))
        except (ValueError, TypeError, AttributeError):
            raise HTTPError(400, "need {heads: [chain_hash], ttl_s: s}")
        return {"protected": tier.protect(heads, min(ttl_s, 60.0))}

    @app.post("/kv/migrate")
    async def kv_migrate(request: Request):
        """Live migration accept (kvnet.migrate): one MIGRATE envelope —
        manifest + CRC-checked block frames — restores into this pod's
        host tier and banks the manifest for its replay. Infrastructure
        route: no admission gate or tenant ledger (the request already
        paid admission on the dying pod; the resumed replay pays this
        pod's gate normally), trace-excluded, refused while draining (a
        dying pod must not accept hand-me-downs it would immediately
        re-ship). Decode + restore run on the default executor — an
        envelope is potentially tens of MB of frames and must not stall
        /health."""
        from ..kvnet import migrate as kv_migrate_mod
        from ..kvnet.client import MAX_BLOCKS_PER_REQUEST

        _require_ready()
        if drainer.draining:
            raise HTTPError(503, "pod is draining; pick another peer",
                            headers={"retry-after": "1"})
        # migrate-storm guard (cheap pre-body probe): a saturated inbox /
        # concurrent-inbound cap answers 429 so a simultaneous multi-pod
        # drain spreads over the other survivors — the shipper's
        # ship_any treats this as "try the next peer", never a failure
        busy_s = service.migrate_busy()
        if busy_s is not None:
            raise HTTPError(429, "migration inbox saturated; try "
                                 "another peer",
                            headers={"retry-after": f"{float(busy_s):g}"})
        body = request.body
        if not body:
            raise HTTPError(400, "empty migration envelope")
        # cheap size bound BEFORE any frame decode (the PR-14 fetch-side
        # lesson, applied to the accept side): an envelope larger than a
        # full legitimate ship — manifest cap + the served block cap at
        # this pod's block size — is refused without paying the decode
        # (which roughly doubles the allocation). Tier-less pods accept
        # manifest-only envelopes, so their bound is the manifest cap.
        tier = service.kv_tier()
        max_body = kv_migrate_mod.MAX_MANIFEST_BYTES + (1 << 16)
        if tier is not None:
            max_body += MAX_BLOCKS_PER_REQUEST * tier.block_nbytes * 2
        if len(body) > max_body:
            raise HTTPError(400, f"migration envelope of {len(body)} "
                                 f"bytes exceeds the {max_body}-byte cap")

        def _accept():
            manifest, entries = kv_migrate_mod.decode_migration(body)
            if len(entries) > MAX_BLOCKS_PER_REQUEST:
                raise kv_migrate_mod.MigrateError(
                    f"envelope carries {len(entries)} blocks, cap is "
                    f"{MAX_BLOCKS_PER_REQUEST}")
            return service.accept_migration(manifest, entries)

        try:
            ack = await asyncio.get_running_loop().run_in_executor(
                None, _accept)
        except kv_migrate_mod.MigrateError as e:
            raise HTTPError(400, f"bad migration envelope: {e}")
        except kv_migrate_mod.MigrateBusy as e:
            # check-then-accept race closed at the real accept gate: a
            # concurrent burst past the pre-body probe still 429s here
            raise HTTPError(429, "migration inbox saturated; try "
                                 "another peer",
                            headers={"retry-after":
                                     f"{e.retry_after_s:g}"})
        if ack is None:
            raise HTTPError(404, "this pod does not accept migrations")
        return ack

    @app.get("/debug/conformance")
    def debug_conformance(request: Request):
        """One-stop conformance verdict: declared budgets vs live reality.
        Joins the HBM ledger, SLO burn rates, and the perf sentinel into a
        single OK/attention payload — what a human curls FIRST on a
        degraded pod, before digging into /debug/flight."""
        tele = service.engine_telemetry()
        out: Dict[str, Any] = {"app": cfg.app}
        hbm = slo = perf = None
        if tele is not None:
            hbm = getattr(tele, "hbm", None)
            slo = getattr(tele, "slo", None)
            perf = getattr(tele, "sentinel", None)
            out["engine"] = tele.snapshot()
        out["hbm"] = hbm.snapshot() if hbm is not None else None
        out["slo"] = slo.snapshot() if slo is not None else None
        out["perf"] = perf.snapshot() if perf is not None else None
        verdict = {
            "hbm_leak_suspect": bool((out["hbm"] or {}).get("leak_suspect")),
            "slo_breach": bool((out["slo"] or {}).get("breach")),
            "perf_degraded": bool((out["perf"] or {}).get("degraded")),
        }
        verdict["ok"] = not any(verdict.values())
        out["verdict"] = verdict
        return out

    @app.get("/debug/faults")
    def debug_faults(request: Request):
        """The live fault-injection schedule (spec, seed, per-clause draw
        and firing counts) — how a chaos run confirms what actually fired."""
        return rz_faults.get().snapshot()

    @app.post("/debug/faults")
    def debug_faults_set(request: Request):
        """Replace the fault schedule at runtime: ``{"spec": "...", "seed"
        : 0}``. Armed only by the SHAI_FAULTS_ENDPOINT env opt-in — a
        production pod must not take fault writes off its serving port."""
        if not rz_faults.endpoint_enabled():
            raise HTTPError(403, "fault injection endpoint is not enabled "
                                 "(set SHAI_FAULTS_ENDPOINT=1)")
        body = request.json()
        try:
            inj = rz_faults.configure(str(body.get("spec", "")),
                                      int(body.get("seed", 0) or 0))
        except (TypeError, ValueError) as e:
            raise HTTPError(400, f"bad fault spec: {e}")
        return inj.snapshot()

    @app.get("/debug/flight")
    def debug_flight(request: Request):
        """Postmortem dump: the last-N completed request timelines (span
        trees, W3C trace ids) + the last-M engine step records + the last
        stops and full collections of the process (``stops``). Bounded
        rings — safe to curl on a degraded pod at any time."""
        n_req = None
        if "requests" in request.query:
            try:
                n_req = max(0, int(request.query["requests"]))
            except ValueError:
                raise HTTPError(400, "requests must be an integer")
        return flight.dump(step_source=service.step_records,
                           n_requests=n_req, stop_source=proc_stops.recent)

    @app.get("/trace/{trace_id}")
    def trace_by_id(request: Request, trace_id: str):
        """This pod's shard of one distributed trace: every flight-ring
        record under ``trace_id`` (dict-indexed — no ring walk). 404 when
        the id never recorded here or has been evicted; cova's fleet
        ``/trace/{id}`` treats that as "no spans from this pod"."""
        traces = flight.traces_for(trace_id)
        if not traces:
            raise HTTPError(404, f"trace {trace_id} not in flight ring")
        return {"trace_id": trace_id, "traces": traces}

    if pub.registry is not None:
        # service gauges read at scrape time — queue depth / pool occupancy
        # become autoscaling signals alongside the request counter
        from prometheus_client.core import GaugeMetricFamily

        class _ServiceStatsCollector:
            def collect(self):
                try:
                    st = service.extra_stats()
                except Exception:
                    return
                for k, v in st.items():
                    if isinstance(v, (int, float)):
                        g = GaugeMetricFamily(
                            f"shai_service_{k}", f"service gauge {k}",
                            labels=["app"])
                        g.add_metric([cfg.app], float(v))
                        yield g

        pub.registry.register(_ServiceStatsCollector())

        from prometheus_client.core import CounterMetricFamily

        class _TenantLedgerCollector:
            """Per-tenant budget/usage gauges off the ledger (bounded
            cardinality by construction — the ledger collapses overflow
            tenants into "other"): the live balance is how a dashboard
            answers "why is this tenant seeing 429s" without log-diving."""

            def collect(self):
                try:
                    snap = ledger.snapshot()
                except Exception:
                    return
                if not snap:
                    return
                tok = CounterMetricFamily(
                    "shai_tenant_tokens_total",
                    "Tokens charged against the tenant budget "
                    "(prompt + generated; 1/request for token-less "
                    "services)", labels=["app", "tenant"])
                infl = GaugeMetricFamily(
                    "shai_tenant_inflight",
                    "Requests in flight per tenant", labels=["app", "tenant"])
                bal = GaugeMetricFamily(
                    "shai_tenant_budget_balance",
                    "Live token-bucket balance (negative = in debt, "
                    "admission refused until refill)",
                    labels=["app", "tenant"])
                for tenant, ent in sorted(snap.items()):
                    tok.add_metric([cfg.app, tenant],
                                   float(ent.get("tokens", 0)))
                    infl.add_metric([cfg.app, tenant],
                                    float(ent.get("inflight", 0)))
                    if "budget_balance" in ent:
                        bal.add_metric([cfg.app, tenant],
                                       float(ent["budget_balance"]))
                yield tok
                yield infl
                yield bal

        pub.registry.register(_TenantLedgerCollector())

    # one trace at a time; concurrent POSTs must not corrupt the session.
    # "task" pins the stop coroutine — the event loop holds tasks weakly,
    # and a GC'd stop task would leave the trace session open forever
    profile_state = {"until": 0.0, "dir": None, "task": None}

    @app.get("/profile")
    def profile_status(request: Request):
        """Profiler session state: clients used to have to probe with a
        POST and read the 409 to learn whether a trace was running. ``dir``
        is the LAST session's trace directory (current session's while one
        runs) so tooling can find the artifact without parsing logs."""
        now = time.time()
        running = now < profile_state["until"] or bool(profile_state["task"])
        return {
            "running": running,
            "seconds_left": round(max(0.0, profile_state["until"] - now), 1),
            "trace_dir": profile_state["dir"],
        }

    @app.post("/profile/{seconds:int}")
    async def profile(request: Request, seconds: int):
        """Capture a ``jax.profiler`` device trace for ``seconds`` while the
        pod keeps serving; the trace lands under the artifact root for
        xprof/tensorboard. SURVEY §5's tracing surface (the reference offers
        only neuron-top/nvitop via kubectl exec) — and the instrument behind
        the perf work (VERDICT r2 next-round #1/#9).
        """
        import os

        if seconds < 1 or seconds > 300:
            raise HTTPError(400, "seconds must be in [1, 300]")
        now = time.time()
        # still-running = countdown not elapsed OR the stop task hasn't
        # completed yet (on a loaded box the window can expire before the
        # event loop runs _stop_later — start_trace would then raise)
        if now < profile_state["until"] or profile_state.get("task"):
            raise HTTPError(409, f"trace already running "
                                 f"({max(0.0, profile_state['until'] - now):.0f}s left)")
        trace_dir = os.path.join(cfg.artifact_root, "traces", cfg.app,
                                 time.strftime("%Y%m%d-%H%M%S"))
        os.makedirs(trace_dir, exist_ok=True)
        import jax

        # arm the lockout only after the trace actually starts — a failed
        # start must not 409-block the endpoint with nothing running
        try:
            jax.profiler.start_trace(trace_dir)
        except RuntimeError as e:
            # profiler held by an out-of-band trace (e.g. a jax.profiler
            # user in-process): same client semantics as our own lockout
            raise HTTPError(409, f"trace already running: {e}")
        profile_state.update(until=now + seconds, dir=trace_dir)

        async def _stop_later():
            await asyncio.sleep(seconds)
            try:
                jax.profiler.stop_trace()
            except Exception:
                log.exception("profiler stop failed")
            finally:
                profile_state["until"] = 0.0
                profile_state["task"] = None

        profile_state["task"] = asyncio.get_running_loop().create_task(
            _stop_later())
        return {"trace_dir": trace_dir, "seconds": seconds,
                "hint": "inspect with: tensorboard --logdir <trace_dir>"}

    @app.get("/serve")
    def serve_ui(request: Request):
        """Interactive page on every model pod — the reference mounts Gradio
        at ``/serve`` on each server (``app/run-sd.py:203``); here it is a
        dependency-free HTML console over the same task route."""
        import json as _json

        example = _json.dumps(service.example_payload() or {"prompt": ""},
                              indent=1)
        html = _SERVE_UI_HTML % {
            "app": cfg.app, "task": service.task,
            "route": service.infer_route, "example": example,
        }
        return Response(html, media_type="text/html")

    # -- model-specific routes --------------------------------------------
    from .asgi import StreamingResponse

    for pattern, methods, handler in service.extra_routes():
        if tuple(methods) == ("GET",):
            # GET-only extra routes are metadata (e.g. /v1/models): no
            # admission gate, no deadline, no lane — an OpenAI SDK client
            # enumerating models must not eat a 429/503 from a pod that is
            # merely busy or draining, and a metadata probe must not
            # inflate the inflight gauge or shai_shed_total
            def _wrap_meta(h):
                async def _meta_handler(request: Request, **params):
                    _require_ready()
                    return h(request, **params)
                return _meta_handler
            app.route(pattern, tuple(methods))(_wrap_meta(handler))
            continue

        def _wrap(h):
            async def _handler(request: Request, **params):
                _require_ready()
                t0 = time.perf_counter()
                scope = _InferScope(request)
                with scope:
                    with obs_trace.span("model_infer", annotation=False):
                        out = await _run_model(lambda: h(request, **params))
                    if isinstance(out, StreamingResponse):
                        # the request stays in flight (and latency runs)
                        # until the stream DRAINS, not when the handler
                        # returns (that's just the submission) — so live
                        # SSE streams count against MAX_INFLIGHT and the
                        # drain actually waits for them
                        release = scope.hand_off_inflight()
                        inner = out.iterator

                        def drained():
                            release()
                            dt = time.perf_counter() - t0
                            collector.record(dt)
                            pub.publish(dt)

                        def timed_iter():
                            try:
                                for chunk in inner:
                                    yield chunk
                            finally:
                                drained()

                        async def timed_aiter():
                            try:
                                async for chunk in inner:
                                    yield chunk
                            finally:
                                # ``async for`` leaves an abandoned async
                                # generator to the collector: close it here,
                                # so its finally-path (the engine cancel)
                                # runs with this one's
                                try:
                                    aclose = getattr(inner, "aclose", None)
                                    if aclose is not None:
                                        await aclose()
                                finally:
                                    drained()

                        out.iterator = (timed_aiter()
                                        if hasattr(inner, "__aiter__")
                                        else timed_iter())
                        return out
                scope.charge(out)
                dt = time.perf_counter() - t0
                collector.record(dt)
                pub.publish(dt)
                tele = service.engine_telemetry()
                if tele is not None:
                    pub.publish_engine(tele)
                return out
            return _handler
        app.route(pattern, tuple(methods))(_wrap(handler))

    return app


def serve_forever(cfg: ServeConfig, service: ModelService) -> None:
    """Pod entrypoint: build the app, start the metrics exporter, serve.

    Installs the SIGTERM graceful-drain path: readiness flips to 503 (the
    LB stops routing), new work sheds with Retry-After, in-flight requests
    finish inside ``cfg.drain_budget_s``, the engine loop drains, then the
    server stops and the process exits 0 — instead of Kubernetes' default
    SIGKILL killing mid-decode requests at the grace-period edge."""
    import signal

    from .httpd import Server

    pub = MetricsPublisher(cfg.app, cfg.nodepool, cfg.pod_name)
    app = create_app(cfg, service, publisher=pub)
    server = Server(app, port=cfg.port)

    def _on_sigterm(signum, frame):
        app.state["begin_drain"](on_done=server.request_shutdown)

    try:
        signal.signal(signal.SIGTERM, _on_sigterm)
    except ValueError:  # not the main thread (embedded/test use)
        log.warning("cannot install SIGTERM drain handler off the main "
                    "thread; relying on the platform grace period")
    server.run()
