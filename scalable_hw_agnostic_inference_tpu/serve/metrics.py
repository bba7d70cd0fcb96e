"""Metric publication — the autoscaling signal.

In the reference, ``cw_pub_metric`` pushes ``{APP}-counter``, ``{NODEPOOL}``
and ``{APP}-latency`` into CloudWatch namespace ``hw-agnostic-infer`` on every
served request, and KEDA scales deployments on ``SUM({app}-counter)``
(reference ``app/run-sd.py:22-37,166-173``, ``sd21-scaledobject.yaml:13-24``;
SURVEY.md §5 "metrics ARE the control plane").

TPU-native equivalent: the same three signals, published two ways at once —

- **Prometheus** (pull): a ``/metrics`` endpoint KEDA's prometheus trigger
  scrapes (``deploy/scale/*.yaml`` use
  ``sum(rate(shai_requests_total{app=...}))``).
- **JSON lines** (push, cloud-agnostic): one line per request on stdout that a
  log-router (CloudWatch EMF, GCP logging metric, fluentbit) turns into a
  counter — preserving the reference's push-model for clusters without a
  Prometheus stack.
"""

from __future__ import annotations

import json
import sys
import threading
import time
from typing import Any, Callable, Dict, Optional

from .. import METRIC_NAMESPACE

try:  # gated: available in the serving image; optional in minimal envs
    from prometheus_client import (
        CollectorRegistry,
        Counter,
        Histogram,
    )

    _HAVE_PROM = True
except Exception:  # pragma: no cover
    _HAVE_PROM = False

_LATENCY_BUCKETS = (
    0.005, 0.01, 0.025, 0.05, 0.1, 0.25, 0.5, 0.9, 1.0, 2.5, 5.0, 10.0, 30.0, 60.0,
)

#: engine-phase histogram metric names on /metrics ← obs.steploop snapshot
#: keys (buckets live with the histograms: obs.steploop.TTFT_BUCKETS etc.)
ENGINE_HISTOGRAMS = {
    "ttft_seconds": ("shai_ttft_seconds",
                     "Time to first token (queue wait included)"),
    "tpot_seconds": ("shai_tpot_seconds",
                     "Per-output-token decode pace after the first token"),
    "queue_wait_seconds": ("shai_queue_wait_seconds",
                           "Submit-to-admission wait in the engine queue"),
    "step_gap_seconds": ("shai_engine_step_gap_seconds",
                         "Inter-step device gap: host time between the last "
                         "blocking readback and the next decode dispatch (0 "
                         "when the dispatch went out ahead of the readback "
                         "or behind a program still running)"),
    "intake_wait_seconds": ("shai_intake_wait_seconds",
                            "Submit on the caller's thread to intake by "
                            "the engine loop, which runs between steps"),
    "stream_wake_seconds": ("shai_engine_stream_wake_seconds",
                            "A streamed token's commit on the engine loop "
                            "to its stream taking it from the queue, on "
                            "the server's event loop (the newest token's, "
                            "where an event carries several)"),
    "stream_encode_seconds": ("shai_engine_stream_encode_seconds",
                              "Taken to handed on as an encoded SSE event "
                              "(text assembly, JSON)"),
    "stream_write_seconds": ("shai_engine_stream_write_seconds",
                             "Handed on to written to the socket (the "
                             "chunked write, behind a full buffer its "
                             "drain)"),
    "stream_deliver_seconds": ("shai_engine_stream_deliver_seconds",
                               "Commit to written: a token's whole way out "
                               "of the program"),
    "stream_finish_lag_seconds": ("shai_engine_stream_finish_lag_seconds",
                                  "A request's future resolved to its "
                                  "stream's last chunk written: how long a "
                                  "caller is still fed after its row is "
                                  "free"),
    "ingress_seconds": ("shai_engine_ingress_seconds",
                        "The ASGI app's first stamp of a request to its "
                        "submit to the engine loop (body, JSON, admission "
                        "gate, lane, tokenising)"),
}
_ENGINE_GAUGES = {
    "running": ("shai_engine_running", "Sequences decoding right now"),
    "waiting": ("shai_engine_waiting", "Requests in the admission queue"),
    "chunking": ("shai_engine_chunking", "Slots mid chunked-prefill"),
    "slots_free": ("shai_engine_slots_free",
                   "Engine rows that hold no sequence (a waiting request "
                   "with a free row is in admission, not in line)"),
    "kv_utilization": ("shai_engine_kv_utilization",
                       "KV page pool fraction held by LIVE sequences "
                       "(evictable prefix-cache blocks excluded — they "
                       "reclaim on demand)"),
    "kv_occupancy": ("shai_engine_kv_occupancy",
                     "KV page pool fraction allocated, cached blocks "
                     "included"),
    "kv_blocks_free": ("shai_engine_kv_blocks_free", "Free KV pool blocks"),
    "spec_acceptance_rate": ("shai_spec_acceptance_rate",
                             "Speculative draft acceptance rate"),
    "pad_fraction": ("shai_engine_pad_fraction",
                     "Fraction of dispatched token slots that were shape "
                     "padding (prefill bucket tails, the paged kernel's "
                     "tile rounding past live tokens, batch pad rows)"),
}
_ENGINE_COUNTERS = {
    "steps": ("shai_engine_steps", "Engine steps executed"),
    "preemptions": ("shai_engine_preemptions",
                    "Recompute-preemptions (KV pool pressure)"),
    "recompiles": ("shai_engine_recompiles",
                   "Post-warm bucket-miss executable compiles"),
    "requests_finished": ("shai_engine_requests_finished",
                          "Requests finished by the engine"),
    "pipeline_flushes": ("shai_engine_pipeline_flushes",
                         "Async-decode lookahead steps retired early by a "
                         "composition/control-flow event"),
    "events_dispatched_ahead": ("shai_engine_events_dispatched_ahead",
                                "Event steps whose prefill or continuation "
                                "program was queued while a decode step was "
                                "still in flight"),
    "first_token_events": ("shai_engine_first_token_events",
                           "Event steps whose decode dispatch met an "
                           "admission's first tokens still on the device"),
    "first_token_events_fed": ("shai_engine_first_token_events_fed",
                               "Those of them whose decode step was "
                               "dispatched before the first tokens were "
                               "read (fed on the device)"),
    "decode_input_uploads": ("shai_engine_decode_input_uploads",
                             "Host-to-device arrays put for decode, verify "
                             "and fused dispatches (a block-table refresh "
                             "counts one)"),
    "tokens_committed": ("shai_engine_tokens_committed",
                         "Output tokens the engine's steps committed, "
                         "whatever path delivers them"),
}
#: pad/real token counters export with a ``phase`` label (prefill /
#: chunk / decode / verify — where in a request's life the pad burned).
#: Any unphased remainder exports under phase="" so the labelled rows
#: always sum exactly to the engine's cumulative totals.
_PAD_PHASE_COUNTERS = {
    "pad_tokens": ("shai_engine_pad_tokens_total",
                   "Padded (wasted) token slots dispatched, cumulative",
                   "pad"),
    "real_tokens": ("shai_engine_real_tokens_total",
                    "Real context/prompt token slots dispatched, "
                    "cumulative",
                    "real"),
}
#: where the engine-loop thread's time goes: cumulative seconds by phase
#: (obs.steploop.PHASES — loop.idle, loop.intake, engine.admit, ...). The
#: phases tile the thread, so the rates over a window sum to one.
_PHASE_SECONDS = ("shai_engine_phase_seconds_total",
                  "Seconds the engine-loop thread spent in each phase")
#: the same thread's CPU seconds over the phases of one step in
#: obs.steploop.CPU_SAMPLE_EVERY, and those phases' wall seconds: for the
#: phases that wait for nothing (obs.steploop.NON_WAITING_PHASES), wall
#: less CPU is time the thread wanted to run and did not
_PHASE_CPU_SECONDS = ("shai_engine_phase_cpu_seconds_total",
                      "CPU seconds of the engine-loop thread in each phase, "
                      "over the sampled steps")
_PHASE_CPU_WALL_SECONDS = ("shai_engine_phase_cpu_wall_seconds_total",
                           "Wall seconds of the phases whose CPU seconds "
                           "were taken")
#: a token's way out and a request's way in (obs.steploop ``stream``)
_STREAM_COUNTERS = ("shai_engine_stream_total",
                    "Streamed responses, by counter: tokens_put, "
                    "tokens_sent, tokens_dropped (an aborted or stopped "
                    "stream's remainder), events_sent (those that carried "
                    "tokens: one may carry several when a stream fell "
                    "behind), bytes_sent, "
                    "streams_started, streams_ended, streams_aborted; "
                    "gauges: backlog (put, neither sent nor dropped), "
                    "draining (future resolved, last byte not written), "
                    "ingress_inflight (requests begun, not yet submitted)")
#: what routing, the attention window and the latent kernel did
#: (obs.steploop ``moe`` / ``window`` / ``mla``): one family each, the
#: snapshot's keys under ``counter``
_MOE_COUNTERS = ("shai_engine_moe_total",
                 "Expert routing in decode dispatches, by counter: "
                 "layer_steps, assignments, experts_touched, load_max, "
                 "streamed_layer_steps; tiled_layer_calls: expert layers "
                 "of prefill and continuation dispatches whose product "
                 "took the tiled form")
_WINDOW_COUNTERS = ("shai_engine_window_total",
                    "Window layers in decode dispatches, by counter: "
                    "tokens_walked, tokens_skipped, tokens_visible, "
                    "pool_tokens_dead (gauge), pool_dead_token_steps, "
                    "pool_token_steps")
_MLA_COUNTERS = ("shai_engine_mla_total",
                 "Latent attention in decode dispatches, by counter: "
                 "layer_steps, tokens_visible")
_KDA_COUNTERS = ("shai_engine_kda_total",
                 "Recurrent (KDA) layers, by counter: prefill_tokens (real "
                 "tokens x KDA layers through the chunked scan), "
                 "chunk_carries (continuation programs that read a slot's "
                 "state), rows_stepped (live rows x KDA layers in decode "
                 "dispatches), slots_live (gauge: arena slots held)")
_SSM_COUNTERS = ("shai_engine_ssm_total",
                 "Recurrent (state-space) layers, by counter: "
                 "prefill_tokens (real tokens x state-space layers through "
                 "the chunked scan), chunk_carries (continuation programs "
                 "that read a slot's state), rows_stepped (live rows x "
                 "state-space layers in decode dispatches), slots_live "
                 "(gauge: arena slots held)")
_CONV_COUNTERS = ("shai_engine_conv_total",
                  "Recurrent (gated short convolution) layers, by counter: "
                  "prefill_tokens (real tokens x conv layers through "
                  "prefill programs), chunk_carries (continuation programs "
                  "that read a slot's tail), rows_stepped (live rows x conv "
                  "layers in decode dispatches), slots_live (gauge: arena "
                  "slots held)")
#: when the pod did not run, and by what it was stopped (obs.stops: the
#: snapshot's ``gc`` and ``stops`` groups, there once the serving app has
#: started the instrument): (family, label, the group's key for a label
#: value). Runbook: a token gap every stream of the pod shares is one of
#: these; ``cause="frozen"`` is the machine's, not the program's.
_PROCESS_COUNTERS = {
    "gc": (
        ("shai_process_gc_pause_seconds_total",
         "Seconds every thread of the pod stood still inside a garbage "
         "collection, by the generation collected (2: the whole heap)",
         "generation", "pause_s_gen{}", ("0", "1", "2")),
        ("shai_process_gc_collections_total",
         "Garbage collections, by the generation collected",
         "generation", "collections_gen{}", ("0", "1", "2"))),
    "stops": (
        ("shai_process_stopped_seconds_total",
         "Seconds the process's heartbeat thread woke late by (over 50 ms "
         "behind a 20 ms sleep), by cause: gc (a collection covers it), "
         "frozen (the process's CPU clock stood still: the machine stopped "
         "it), starved (a thread kept the interpreter lock)",
         "cause", "{}_s", ("frozen", "starved", "gc")),
        ("shai_process_stops_total",
         "Late wakes of the heartbeat thread, by cause",
         "cause", "count_{}", ("frozen", "starved", "gc"))),
}
#: engine steps whose duration passed ten times the step ring's median and
#: 0.1 s (obs.steploop ``stall``), by the phase that held most of the step
#: (``fetch``: the device or a read; anything else: the host), and the
#: seconds they ran over the median
_STALLED_STEPS = ("shai_engine_stalled_steps_total",
                  "Engine steps that took over ten times the step ring's "
                  "median duration (and over 0.1 s), by the step's longest "
                  "phase")
_STALLED_SECONDS = ("shai_engine_stalled_seconds_total",
                    "Seconds stalled steps ran over the median step")
#: conformance-layer gauge families: each instrument riding the engine
#: telemetry object exports its flat numeric snapshot verbatim under a
#: prefix — obs.slo → shai_slo_* (per-objective burn rates + breach),
#: obs.hbm → shai_hbm_* (per-pool bytes, headroom, fragmentation, leak
#: flag), obs.sentinel → shai_perf_* (live/projected tok/s, conformance)
_CONFORMANCE_PREFIXES = (
    ("slo", "shai_slo_", "SLO burn-rate engine gauge"),
    ("hbm", "shai_hbm_", "Live HBM ledger gauge"),
    ("sentinel", "shai_perf_", "Perf-model sentinel gauge"),
)
#: host KV tier (kvtier.pool.HostKVTier snapshot keys → metric names):
#: counters carry the Prometheus _total suffix; gauges export raw
_KVTIER_COUNTERS = {
    "hits": ("shai_kvtier_hits_total",
             "Host KV tier: prefix blocks found resident"),
    "misses": ("shai_kvtier_misses_total",
               "Host KV tier: prefix walks that stopped short"),
    "evictions": ("shai_kvtier_evictions_total",
                  "Host KV tier: blocks LRU-evicted from the host pool"),
    "stores": ("shai_kvtier_stores_total",
               "Host KV tier: blocks demoted into the host pool"),
    "restored": ("shai_kvtier_restored_total",
                 "Host KV tier: blocks swapped back into the device pool"),
    "bytes": ("shai_kvtier_bytes_total",
              "Host KV tier: cumulative bytes copied into the host pool"),
    "errors": ("shai_kvtier_errors_total",
               "Host KV tier: failures degraded to recompute"),
    "dropped": ("shai_kvtier_dropped_total",
                "Host KV tier: demotions dropped (queue full / no capacity)"),
}
#: network KV transport (kvnet.client.KvNetStats snapshot keys): the
#: disaggregated-serving counters — fetched/served block flow, transport
#: bytes, and the degrade signal (fallbacks = fetches that fell back to
#: local recompute)
_KVNET_COUNTERS = {
    "fetched": ("shai_kvnet_fetched_total",
                "kvnet: KV blocks pulled from peer pods into the host "
                "tier"),
    "served": ("shai_kvnet_served_total",
               "kvnet: host-tier KV blocks served to peers over "
               "/kv/blocks"),
    "bytes": ("shai_kvnet_bytes_total",
              "kvnet: frame bytes moved through this pod's transport "
              "(served out + fetched in)"),
    "errors": ("shai_kvnet_errors_total",
               "kvnet: transport failures (connect/read/corrupt frames)"),
    "fallbacks": ("shai_kvnet_fallbacks_total",
                  "kvnet: fetches degraded to local recompute (open "
                  "breaker, transport failure, rejected frames)"),
}
#: live migration (kvnet.migrate.MigrateStats snapshot keys): the drain
#: ladder's counters — shipped/received/resumed move on the happy path,
#: failed counts ships that never landed, fallbacks counts ladder
#: degradations (no peer, refused restore)
_MIGRATE_COUNTERS = {
    "shipped": ("shai_migrate_shipped_total",
                "migrate: in-flight requests shipped to a peer at drain"),
    "received": ("shai_migrate_received_total",
                 "migrate: migration envelopes accepted from peers"),
    "resumed": ("shai_migrate_resumed_total",
                "migrate: migrated sequences re-admitted and completed "
                "on this pod"),
    "failed": ("shai_migrate_failed_total",
               "migrate: ship attempts that never landed on a peer"),
    "fallbacks": ("shai_migrate_fallbacks_total",
                  "migrate: ladder degradations (no peer, refused "
                  "restore, unencodable blocks) — each one recomputed "
                  "instead of failing"),
    "busy": ("shai_migrate_peer_busy_total",
             "migrate: 429 answers from saturated peers (inbox full or "
             "at SHAI_MIGRATE_MAX_INBOUND) — back-pressure the shipper "
             "routed around, never a failure"),
}
#: KV fabric (kvnet.directory.KvFabricStats snapshot keys): the fleet-
#: wide prefix-pool counters. Runbook: rising stale_holders = the
#: directory TTL outlives the pools (shorten SHAI_KVFABRIC_TTL_S);
#: rising remote_misses with flat stale_holders = holders unreachable —
#: under-replication (lower SHAI_KVFABRIC_HOT_N / add capacity)
_KVFABRIC_COUNTERS = {
    "probes": ("shai_kvfabric_probes_total",
               "KV fabric: peer-probe admissions attempted (the ladder's "
               "third rung)"),
    "remote_hits": ("shai_kvfabric_remote_hits_total",
                    "KV fabric: probes that landed a remote KV run"),
    "remote_misses": ("shai_kvfabric_remote_misses_total",
                      "KV fabric: probes that came up empty and "
                      "recomputed"),
    "replications": ("shai_kvfabric_replications_total",
                     "KV fabric: hot-prefix runs pulled by background "
                     "replication (/kv/pull)"),
    "directory_size": ("shai_kvfabric_directory_size_total",
                       "KV fabric: chain heads in this pod's local "
                       "directory"),
    "stale_holders": ("shai_kvfabric_stale_holders_total",
                      "KV fabric: holders that answered but no longer "
                      "held the advertised run"),
}
_KVTIER_GAUGES = {
    "used_bytes": ("shai_kvtier_used_bytes",
                   "Host KV tier: bytes resident in the host pool"),
    "capacity_bytes": ("shai_kvtier_capacity_bytes",
                       "Host KV tier: configured capacity "
                       "(SHAI_KVTIER_BYTES)"),
    "entries": ("shai_kvtier_entries", "Host KV tier: resident blocks"),
    "utilization": ("shai_kvtier_utilization",
                    "Host KV tier: used/capacity fraction"),
    "hit_rate": ("shai_kvtier_hit_rate",
                 "Host KV tier: hits / (hits + misses)"),
}
#: multi-tenant QoS: per-tenant attribution off the engine telemetry
#: (bounded label cardinality — obs.steploop.MAX_TENANT_LABELS tenants
#: plus "other"; the ledger-side gauges export from serve.app)
_TENANT_COUNTERS = {
    "requests": ("shai_tenant_requests_total",
                 "Requests submitted to the engine, per tenant"),
}
_TENANT_GAUGES = {
    "waiting": ("shai_tenant_waiting",
                "Engine queue depth held by this tenant (last step)"),
    "running": ("shai_tenant_running",
                "Decoding slots held by this tenant (last step)"),
}
_TENANT_TTFT = ("shai_tenant_ttft_seconds",
                "Time to first token per tenant (queue wait included) — "
                "the fairness number: a flooding tenant's queue must not "
                "move another tenant's TTFT")


class EngineTelemetryCollector:
    """Prometheus custom collector over an ``obs.steploop.StepTelemetry``.

    ``provider`` is a zero-arg callable returning the telemetry (or None
    before the engine loads) — resolved at scrape time, so registration can
    happen before ``service.load()`` built the engine.
    """

    def __init__(self, provider: Callable[[], Any], app: str):
        self.provider = provider
        self.app = app

    def collect(self):
        from prometheus_client.core import (
            CounterMetricFamily,
            GaugeMetricFamily,
            HistogramMetricFamily,
        )

        try:
            tele = self.provider()
        except Exception:
            return
        if tele is None:
            return
        snap = tele.snapshot()
        for key, (name, doc) in _ENGINE_GAUGES.items():
            if key in snap:
                g = GaugeMetricFamily(name, doc, labels=["app"])
                g.add_metric([self.app], float(snap[key]))
                yield g
        for key, (name, doc) in _ENGINE_COUNTERS.items():
            c = CounterMetricFamily(name, doc, labels=["app"])
            c.add_metric([self.app], float(snap.get(key, 0)))
            yield c
        phases = snap.get("pad_by_phase") or {}
        for key, (name, doc, col) in _PAD_PHASE_COUNTERS.items():
            c = CounterMetricFamily(name, doc, labels=["app", "phase"])
            total = float(snap.get(key, 0))
            phased = 0.0
            for phase in sorted(phases):
                v = float(phases[phase].get(col, 0))
                phased += v
                c.add_metric([self.app, phase], v)
            if total - phased or not phases:
                c.add_metric([self.app, ""], total - phased)
            yield c
        c = CounterMetricFamily(*_PHASE_SECONDS, labels=["app", "phase"])
        for phase, secs in sorted((snap.get("phase_s") or {}).items()):
            c.add_metric([self.app, phase], float(secs))
        yield c
        for key, family in (("phase_cpu_s", _PHASE_CPU_SECONDS),
                            ("phase_cpu_wall_s", _PHASE_CPU_WALL_SECONDS)):
            c = CounterMetricFamily(*family, labels=["app", "phase"])
            for phase, secs in sorted((snap.get(key) or {}).items()):
                c.add_metric([self.app, phase], float(secs))
            yield c
        for key, family in (("stream", _STREAM_COUNTERS),
                            ("moe", _MOE_COUNTERS),
                            ("window", _WINDOW_COUNTERS),
                            ("mla", _MLA_COUNTERS),
                            ("kda", _KDA_COUNTERS),
                            ("ssm", _SSM_COUNTERS),
                            ("conv", _CONV_COUNTERS)):
            if snap.get(key):
                c = CounterMetricFamily(*family, labels=["app", "counter"])
                for counter, v in sorted(snap[key].items()):
                    c.add_metric([self.app, counter], float(v))
                yield c
        for group, families in _PROCESS_COUNTERS.items():
            if group in snap:
                for name, doc, label, key, values in families:
                    c = CounterMetricFamily(name, doc, labels=["app", label])
                    for v in values:
                        c.add_metric([self.app, v],
                                     float(snap[group].get(key.format(v), 0)))
                    yield c
        stall = snap.get("stall")
        if stall is not None:
            c = CounterMetricFamily(*_STALLED_STEPS, labels=["app", "phase"])
            for phase, n in sorted(stall["steps_by_phase"].items()):
                c.add_metric([self.app, phase], float(n))
            yield c
            c = CounterMetricFamily(*_STALLED_SECONDS, labels=["app"])
            c.add_metric([self.app], float(stall["excess_s"]))
            yield c
        hists = tele.histograms()
        for key, (name, doc) in ENGINE_HISTOGRAMS.items():
            hs = hists.get(key)
            if hs is None:
                continue
            h = HistogramMetricFamily(name, doc, labels=["app"])
            h.add_metric(
                [self.app],
                [(str(le) if le != "+Inf" else "+Inf", float(c))
                 for le, c in hs["buckets"]],
                sum_value=float(hs["sum"]))
            yield h
        # conformance layer (PR 7): SLO burn rates, HBM ledger, perf
        # sentinel — attached to the telemetry object by the engine; a
        # tier without a given instrument simply exports nothing for it
        for attr, prefix, doc in _CONFORMANCE_PREFIXES:
            obj = getattr(tele, attr, None)
            if obj is None:
                continue
            try:
                snap = obj.snapshot()
            except Exception:
                continue
            for k, v in snap.items():
                if isinstance(v, bool) or not isinstance(v, (int, float)):
                    continue
                g = GaugeMetricFamily(f"{prefix}{k}", doc, labels=["app"])
                g.add_metric([self.app], float(v))
                yield g
        # multi-tenant QoS: per-tenant request counts, queue/slot gauges,
        # and TTFT histograms — present only once a tenant tag (or QoS)
        # was seen, absent entirely on single-tenant pods
        tsnap = tele.tenant_snapshot() if hasattr(tele, "tenant_snapshot") \
            else {}
        if tsnap:
            for key, (name, doc) in _TENANT_COUNTERS.items():
                c = CounterMetricFamily(name, doc, labels=["app", "tenant"])
                for tenant, ent in sorted(tsnap.items()):
                    c.add_metric([self.app, tenant], float(ent.get(key, 0)))
                yield c
            for key, (name, doc) in _TENANT_GAUGES.items():
                g = GaugeMetricFamily(name, doc, labels=["app", "tenant"])
                for tenant, ent in sorted(tsnap.items()):
                    g.add_metric([self.app, tenant], float(ent.get(key, 0)))
                yield g
            h = HistogramMetricFamily(_TENANT_TTFT[0], _TENANT_TTFT[1],
                                      labels=["app", "tenant"])
            for tenant, hs in sorted(tele.tenant_histograms().items()):
                h.add_metric(
                    [self.app, tenant],
                    [(str(le) if le != "+Inf" else "+Inf", float(c))
                     for le, c in hs["buckets"]],
                    sum_value=float(hs["sum"]))
            yield h
        # network KV transport (kvnet): the disaggregated-serving counter
        # families, riding the same telemetry object — absent entirely on
        # pods outside the network KV plane
        kvn = getattr(tele, "kvnet", None)
        if kvn is not None:
            try:
                snap = kvn.snapshot()
            except Exception:
                snap = None
            if snap is not None:
                for key, (name, doc) in _KVNET_COUNTERS.items():
                    c = CounterMetricFamily(name, doc, labels=["app"])
                    c.add_metric([self.app], float(snap.get(key, 0)))
                    yield c
        # live migration (kvnet.migrate): the drain ladder's counter
        # families — attached by the engine, absent on engine-less pods
        mig = getattr(tele, "migrate", None)
        if mig is not None:
            try:
                snap = mig.snapshot()
            except Exception:
                snap = None
            if snap is not None:
                for key, (name, doc) in _MIGRATE_COUNTERS.items():
                    c = CounterMetricFamily(name, doc, labels=["app"])
                    c.add_metric([self.app], float(snap.get(key, 0)))
                    yield c
        # KV fabric (kvnet.directory): the fleet prefix-pool counters —
        # attached by the engine only when the fabric is armed, so a
        # fabric-off pod exports no shai_kvfabric_* family at all
        fab = getattr(tele, "kvfabric", None)
        if fab is not None:
            try:
                snap = fab.snapshot()
            except Exception:
                snap = None
            if snap is not None:
                for key, (name, doc) in _KVFABRIC_COUNTERS.items():
                    c = CounterMetricFamily(name, doc, labels=["app"])
                    c.add_metric([self.app], float(snap.get(key, 0)))
                    yield c
        # host KV tier (kvtier): counters with their _total contract +
        # occupancy gauges, from the same telemetry object
        kvt = getattr(tele, "kvtier", None)
        if kvt is not None:
            try:
                snap = kvt.snapshot()
            except Exception:
                return
            for key, (name, doc) in _KVTIER_COUNTERS.items():
                c = CounterMetricFamily(name, doc, labels=["app"])
                c.add_metric([self.app], float(snap.get(key, 0)))
                yield c
            for key, (name, doc) in _KVTIER_GAUGES.items():
                if key in snap:
                    g = GaugeMetricFamily(name, doc, labels=["app"])
                    g.add_metric([self.app], float(snap[key]))
                    yield g


#: request-reliability (resilience.idempotency): cache-counter key ->
#: exported family; the gauge rides separately below
_IDEMP_COUNTERS = {
    "replayed_total": ("shai_idemp_replayed_total",
                       "keyed duplicates answered from the completion "
                       "cache (no re-execution, no second charge)"),
    "joined_total": ("shai_idemp_joined_total",
                     "keyed duplicates that joined an in-flight "
                     "execution"),
    "misses_total": ("shai_idemp_misses_total",
                     "new idempotency keys (executions claimed)"),
    "evicted_total": ("shai_idemp_evicted_total",
                      "entries dropped by the bound or the TTL sweep"),
    "lookup_errors_total": ("shai_idemp_lookup_errors_total",
                            "lookups degraded to a miss (at-least-once "
                            "fallback)"),
}
_IDEMP_ENTRIES = ("shai_idemp_entries",
                  "live completion-cache entries (bounded by "
                  "SHAI_IDEMP_CACHE)")


class IdempotencyCollector:
    """Prometheus collector over ``resilience.idempotency``'s per-pod
    completion cache — same lazy-provider contract as
    :class:`EngineTelemetryCollector`."""

    def __init__(self, provider: Callable[[], Any], app: str):
        self.provider = provider
        self.app = app

    def collect(self):
        from prometheus_client.core import (
            CounterMetricFamily,
            GaugeMetricFamily,
        )

        try:
            cache = self.provider()
            snap = cache.snapshot() if cache is not None else None
        except Exception:
            return
        if snap is None:
            return
        for key, (name, doc) in _IDEMP_COUNTERS.items():
            c = CounterMetricFamily(name, doc, labels=["app"])
            c.add_metric([self.app], float(snap.get(key, 0)))
            yield c
        g = GaugeMetricFamily(_IDEMP_ENTRIES[0], _IDEMP_ENTRIES[1],
                              labels=["app"])
        g.add_metric([self.app], float(snap.get("entries", 0)))
        yield g


class MetricsPublisher:
    """Publishes the request counter + latency signals for one serving pod."""

    def __init__(
        self,
        app: str,
        nodepool: str,
        pod_name: str = "",
        emit_json: bool = True,
        registry: Optional["CollectorRegistry"] = None,
        stream=None,
    ):
        self.app = app
        self.nodepool = nodepool
        self.pod_name = pod_name
        self.emit_json = emit_json
        self._stream = stream or sys.stdout
        self._lock = threading.Lock()
        self._served = 0
        self.registry = None
        if _HAVE_PROM:
            self.registry = registry or CollectorRegistry()
            self._prom_requests = Counter(
                "shai_requests_total",
                "Served requests (the KEDA scaling signal)",
                ["app", "nodepool", "pod"],
                registry=self.registry,
            )
            self._prom_latency = Histogram(
                "shai_request_latency_seconds",
                "Per-request latency",
                ["app", "nodepool"],
                buckets=_LATENCY_BUCKETS,
                registry=self.registry,
            )
            # speculative decoding counters (drafted/accepted/committed):
            # the KEDA-visible signal pair behind acceptance rate — a tier
            # whose acceptance collapses decodes at vanilla pace and needs
            # MORE replicas per token served, so the autoscaler must see it
            self._prom_spec = {
                kind: Counter(
                    f"shai_spec_{kind}_total",
                    f"Speculative decoding: {kind} tokens",
                    ["app", "nodepool", "pod"],
                    registry=self.registry,
                )
                for kind in ("drafted", "accepted", "committed")
            }
            # load-shedding counter (resilience.admission): requests
            # refused at the door — per reason, so dashboards can split a
            # drain's 503s from an overload's 429s (runbook: README
            # "Resilience"; this is the pod-level twin of the failover
            # controller's overload trigger)
            self._prom_shed = Counter(
                "shai_shed_total",
                "Requests shed by the admission gate / drain",
                # tenant label (multi-tenant QoS): bounded upstream — the
                # serve layer passes ledger-sanitized tenant keys only, so
                # cardinality is capped at SHAI_QOS_MAX_TENANTS + "other"
                ["app", "nodepool", "reason", "tenant"],
                registry=self.registry,
            )
        self._spec_last = {"drafted": 0, "accepted": 0, "committed": 0}
        self._engine_last_steps = -1

    @property
    def served(self) -> int:
        with self._lock:
            return self._served

    def publish(self, latency_s: float, count: int = 1) -> None:
        """Record ``count`` served requests at ``latency_s`` seconds each."""
        with self._lock:
            self._served += count
        if _HAVE_PROM and self.registry is not None:
            self._prom_requests.labels(self.app, self.nodepool, self.pod_name).inc(count)
            self._prom_latency.labels(self.app, self.nodepool).observe(latency_s)
        if self.emit_json:
            # fixed metadata outside, the reference's three dynamically-named
            # CloudWatch metrics inside "data" (setdefault so a pathological
            # NODEPOOL equal to "{app}-counter" can't silently drop a signal)
            data = {f"{self.app}-counter": count}
            data.setdefault(self.nodepool, count)
            data[f"{self.app}-latency"] = round(latency_s, 4)
            line = json.dumps(
                {
                    "ns": METRIC_NAMESPACE,
                    "ts": round(time.time(), 3),
                    "pod": self.pod_name,
                    "data": data,
                }
            )
            print(line, file=self._stream, flush=True)

    def count_shed(self, reason: str, tenant: str = "") -> None:
        """Record one shed (refused) request under ``reason`` — exported as
        ``shai_shed_total{reason=...,tenant=...}`` and one JSON line for
        the push-model path (overloads are exactly when the control plane
        needs to see per-pod shed rates — and per-tenant shed rates are
        how a dashboard separates 'the pod is saturated' from 'one tenant
        is over budget'). ``tenant`` must arrive bounded (the serve layer
        passes the ledger's sanitized key); empty reads as ``default``."""
        if _HAVE_PROM and self.registry is not None:
            self._prom_shed.labels(self.app, self.nodepool, reason,
                                   tenant or "default").inc()
        if self.emit_json:
            # reason rides in the metric NAME: "data" is a name -> number
            # map for the CloudWatch-style consumer (a string value would
            # break its float() ingestion), mirroring the Prometheus twin's
            # reason label
            print(json.dumps({
                "ns": METRIC_NAMESPACE,
                "ts": round(time.time(), 3),
                "pod": self.pod_name,
                "data": {f"{self.app}-shed-{reason}": 1},
            }), file=self._stream, flush=True)

    def publish_spec(self, drafted: int, accepted: int,
                     committed: int) -> None:
        """Record CUMULATIVE speculative-decoding counters (the engine's
        ``SpecStats`` totals); Prometheus counters advance by the delta
        since the last call, and the JSON push path emits the cumulative
        snapshot plus the derived acceptance rate. Idempotent per snapshot —
        callers just forward the engine's current totals after each request.
        """
        # delta AND emission both under the lock: a concurrent publisher
        # finishing between them would print cumulative snapshots out of
        # order (totals going backwards on the push stream)
        with self._lock:
            cur = {"drafted": drafted, "accepted": accepted,
                   "committed": committed}
            delta = {k: max(0, cur[k] - self._spec_last[k]) for k in cur}
            self._spec_last = cur
            if not any(delta.values()):
                return
            if _HAVE_PROM and self.registry is not None:
                for kind, d in delta.items():
                    if d:
                        self._prom_spec[kind].labels(
                            self.app, self.nodepool, self.pod_name).inc(d)
            if self.emit_json:
                data = {f"{self.app}-spec-{k}": v for k, v in cur.items()}
                data[f"{self.app}-spec-acceptance"] = (
                    round(accepted / drafted, 4) if drafted else 0.0)
                print(json.dumps({
                    "ns": METRIC_NAMESPACE,
                    "ts": round(time.time(), 3),
                    "pod": self.pod_name,
                    "data": data,
                }), file=self._stream, flush=True)

    def attach_engine_telemetry(self, provider: Callable[[], Any]) -> bool:
        """Register the engine's step telemetry on this publisher's
        Prometheus registry (TTFT/TPOT/queue-wait histograms + step gauges
        and counters). ``provider`` resolves lazily at scrape time so the
        app factory can attach before the engine exists. Returns False when
        prometheus_client is unavailable (the JSON-line path —
        :meth:`publish_engine` — still works there)."""
        if not (_HAVE_PROM and self.registry is not None):
            return False
        self.registry.register(EngineTelemetryCollector(provider, self.app))
        return True

    def attach_idempotency(self, provider: Callable[[], Any]) -> bool:
        """Register the per-pod idempotency cache's counter families
        (``shai_idemp_*``) — the lazy-provider contract of
        :meth:`attach_engine_telemetry`."""
        if not (_HAVE_PROM and self.registry is not None):
            return False
        self.registry.register(IdempotencyCollector(provider, self.app))
        return True

    def publish_engine(self, tele: Any) -> None:
        """Emit one JSON line of engine step telemetry (the push-model twin
        of the Prometheus collector, for clusters scaling off a log
        router). Deduped on the step counter: a snapshot identical in step
        count to the last published one is dropped, so request bursts don't
        multiply identical lines. Accepts either a snapshot dict or the
        live telemetry object (``.steps`` / ``.snapshot()``); with the
        object form, deduped hot-path calls pay one int compare instead of
        building a snapshot that would be thrown away."""
        if not self.emit_json:
            return
        with self._lock:
            is_dict = isinstance(tele, dict)
            steps = tele.get("steps", 0) if is_dict else tele.steps
            if steps == self._engine_last_steps:
                return
            self._engine_last_steps = steps
            snapshot = tele if is_dict else tele.snapshot()
            data = {f"{self.app}-engine-{k}": v
                    for k, v in snapshot.items()
                    if isinstance(v, (int, float))}
            print(json.dumps({
                "ns": METRIC_NAMESPACE,
                "ts": round(time.time(), 3),
                "pod": self.pod_name,
                "data": data,
            }), file=self._stream, flush=True)
