"""Capacity-checker: the two-state failover/fallback routing controller.

Reference semantics (``capacity-checker-deploy.yaml:26-49``,
``capacity-checker-config.yaml:24-44``; formalized ``README.md:276-316``):

- every poll interval, look for **insufficient-capacity provisioning
  events** for the accelerator nodepools; on a hit, switch the stack from
  cost-optimized (weighted routing + weighted scaledobjects) to
  capacity-optimized (equal routing + equal scaledobjects)  — FAILOVER;
- once in failover, when the synthetic-load deployment's readyReplicas
  indicates a fresh demand cycle (in [lo, hi]), switch back — FALLBACK.

The reference reads CloudWatch Logs Insights over Karpenter logs; the
TPU/GKE-native signal is Kubernetes events (``FailedScaleUp``,
``NotTriggerScaleUp``, Karpenter's ``insufficient capacity`` NodeClaim
events). The decision core is pure (:func:`decide`) and unit-tested with
fake events (SURVEY.md §4's fake-cluster implication); the k8s glue shells
out to kubectl exactly like the reference.
"""

from __future__ import annotations

import dataclasses
import json
import logging
import subprocess
import time
from typing import Dict, List, Optional, Sequence

log = logging.getLogger(__name__)

INSUFFICIENT_MARKERS = (
    "insufficient capacity",        # Karpenter NodeClaim failure text
    "FailedScaleUp",                # cluster-autoscaler event reason
    "NotTriggerScaleUp",
    "GCE_STOCKOUT",                 # GKE TPU stockout
    "does not have enough resources",
)


@dataclasses.dataclass(frozen=True)
class Event:
    reason: str
    message: str
    involved: str = ""              # node/nodepool/nodeclaim name


@dataclasses.dataclass
class ControllerState:
    mode: str = "weighted"          # "weighted" (cost) | "equal" (capacity)
    last_trigger: str = ""


@dataclasses.dataclass(frozen=True)
class OverloadThresholds:
    """When a pod's engine telemetry (serve ``/stats`` → ``engine`` section,
    the obs.steploop snapshot) reads saturated: a sustained admission queue
    OR a KV pool at the preemption edge. These are leading indicators —
    they move minutes before the request-rate trigger sees refused work."""

    max_queue_depth: float = 8.0       # waiting requests on one pod
    max_kv_utilization: float = 0.95   # page pool fraction in use


def slo_breached(stats: Optional[dict]) -> bool:
    """One pod's merged snapshot → latency SLO burning? The ``slo_breach``
    key is merged in by :func:`fetch_engine_stats` from the pod's
    ``/stats`` → ``"slo"`` section (the obs.slo burn-rate engine: fast
    5 m AND slow 1 h windows both over budget). Absent telemetry — pod
    without SLO targets, old image — reads healthy; note the pod-local
    admission gate sees the raw engine snapshot (no ``slo_breach`` key),
    so a latency breach reroutes the FLEET without also shedding at the
    door of the pod that is still serving."""
    return isinstance(stats, dict) and bool(stats.get("slo_breach"))


def queue_depth(stats: dict) -> float:
    """The line behind full rows: waiting requests beyond the rows that stand
    free for them. A burst that finishes many rows in one step leaves as many
    callers waiting for ONE admission step, not queued behind anyone; pricing
    them as a line shed a closed loop of ``rows + 4`` callers whenever its
    answers ended together. A snapshot without ``slots_free`` (an older
    image) counts every waiting request, as before."""
    return stats.get("waiting", 0) - stats.get("slots_free", 0)


def is_overloaded(stats: Optional[dict],
                  th: OverloadThresholds = OverloadThresholds()) -> bool:
    """One pod's engine snapshot → saturated? Missing/partial snapshots
    (pod loading, old image) read as healthy — absence of telemetry must
    not flap the routing mode. A merged latency-SLO breach (see
    :func:`slo_breached`) counts as saturation too: a tier missing its own
    TTFT/TPOT targets needs traffic moved exactly like a full queue."""
    if not isinstance(stats, dict):
        return False
    if queue_depth(stats) > th.max_queue_depth:
        return True
    if slo_breached(stats):
        return True
    return stats.get("kv_utilization", 0.0) > th.max_kv_utilization


def is_capacity_failure(ev: Event, nodepool_substrings: Sequence[str]) -> bool:
    text = f"{ev.reason} {ev.message}"
    if not any(m.lower() in text.lower() for m in INSUFFICIENT_MARKERS):
        return False
    if not nodepool_substrings:
        return True
    hay = f"{ev.involved} {ev.message}".lower()
    return any(s.lower() in hay for s in nodepool_substrings)


def decide(state: ControllerState, events: List[Event],
           load_ready_replicas: Optional[int],
           nodepool_substrings: Sequence[str] = (),
           fresh_cycle: range = range(1, 6),
           engine_stats: Optional[Sequence[Optional[dict]]] = None,
           thresholds: OverloadThresholds = OverloadThresholds()) -> str:
    """Pure decision → action: "failover" | "fallback" | "hold".

    Mirrors the reference's two rules exactly (``capacity-checker-deploy.
    yaml:30-47``): capacity failure in cost mode → failover; fresh demand
    cycle while failed-over → fallback. Does NOT mutate ``state`` — callers
    :func:`commit` only after the cluster apply succeeds, so a failed apply
    retries next poll instead of desyncing controller from cluster.

    ``engine_stats`` (optional, one obs snapshot per serving pod — see
    :func:`fetch_engine_stats`) adds a third, leading trigger: a majority of
    pods saturated (queue depth / KV utilization past ``thresholds``) while
    cost-optimized fails over BEFORE provisioning events appear — the
    raw-request-rate signal the reference scales on cannot see a pool that
    is full but not yet refusing.
    """
    failures = [e for e in events if is_capacity_failure(e, nodepool_substrings)]
    if state.mode == "weighted" and failures:
        state.last_trigger = failures[0].message[:200]
        return "failover"
    if state.mode == "weighted" and engine_stats:
        # latency-driven trigger first (distinct label): a majority of pods
        # burning their SLO budget fails over even with empty queues — a
        # tier can be slow without being full (perf regression, thermal
        # throttle, drafter collapse), and the burn-rate engine is the
        # only signal that sees it
        burning = sum(1 for s in engine_stats if slo_breached(s))
        if burning * 2 > len(engine_stats):
            state.last_trigger = (
                f"slo burn-rate breach on {burning}/{len(engine_stats)} pods")
            return "failover"
        hot = sum(1 for s in engine_stats if is_overloaded(s, thresholds))
        if hot * 2 > len(engine_stats):  # strict majority: one hot pod is
            state.last_trigger = (       # a scheduling blip, not capacity
                f"engine overload on {hot}/{len(engine_stats)} pods")
            return "failover"
    if state.mode == "equal" and load_ready_replicas is not None \
            and load_ready_replicas in fresh_cycle:
        state.last_trigger = f"load readyReplicas={load_ready_replicas}"
        return "fallback"
    return "hold"


def commit(state: ControllerState, action: str) -> None:
    """Record a successfully applied transition."""
    if action == "failover":
        state.mode = "equal"
    elif action == "fallback":
        state.mode = "weighted"


# -- controller error accounting + retry pacing -----------------------------

#: cumulative failed iterations (process-local); mirrored to the
#: ``shai_controller_errors_total`` Prometheus counter when the client is
#: available — a broken kubeconfig becomes a visible, alertable rate
#: instead of a silent 5-minute crash loop
_controller_errors = 0
_prom_errors = None


def controller_errors_total() -> int:
    return _controller_errors


def count_controller_error() -> None:
    global _controller_errors, _prom_errors
    _controller_errors += 1
    if _prom_errors is None:
        try:
            from prometheus_client import Counter

            _prom_errors = Counter(
                "shai_controller_errors_total",
                "capacity-checker iterations that raised")
        except Exception:
            _prom_errors = False  # unavailable (or duplicate): int only
    if _prom_errors:
        _prom_errors.inc()


def failure_backoff_s(consecutive_failures: int, base_s: float = 2.0,
                      cap_s: float = 300.0) -> float:
    """Retry pacing while the control loop is broken: quick retries first
    (a transient apiserver blip recovers in seconds, not a full poll
    interval), doubling up to ``cap_s``. Pure — unit-tested directly."""
    if consecutive_failures <= 0:
        return 0.0
    return min(cap_s, base_s * (2 ** (consecutive_failures - 1)))


def start_metrics_exporter() -> bool:
    """Serve prometheus_client's default registry (which holds
    ``shai_controller_errors_total``) from the controller process — it
    runs no MetricsPublisher, so without this the counter would increment
    into a registry nobody scrapes. ``CONTROLLER_METRICS_PORT`` (default
    9101, 0 disables). Returns True when the exporter is up."""
    import os

    from ..obs.util import env_int

    # shai-lint: allow(env-knob) "" must keep DISABLING the exporter (the
    # blank-the-knob deployment convention predates the registry; the
    # lenient parsers deliberately read "" as unset-use-default)
    if os.environ.get("CONTROLLER_METRICS_PORT") == "":
        return False
    port = env_int("CONTROLLER_METRICS_PORT", 9101)
    if not port:
        return False
    try:
        from prometheus_client import start_http_server

        start_http_server(port)
        log.info("controller metrics on :%d", port)
        return True
    except Exception:
        log.warning("controller metrics exporter unavailable", exc_info=True)
        return False


# -- k8s glue (shell-out, matching the reference's kubectl-apply loop) ------

def kubectl(*args: str) -> str:
    out = subprocess.run(["kubectl", *args], capture_output=True, text=True)
    if out.returncode != 0:
        raise RuntimeError(f"kubectl {' '.join(args)}: {out.stderr.strip()}")
    return out.stdout


def fetch_events(namespace: str = "default") -> List[Event]:
    raw = kubectl("get", "events", "-n", namespace, "-o", "json",
                  "--field-selector", "type=Warning")
    items = json.loads(raw).get("items", [])
    return [Event(reason=i.get("reason", ""),
                  message=i.get("message", ""),
                  involved=i.get("involvedObject", {}).get("name", ""))
            for i in items]


def fetch_load_ready(deployment: str, namespace: str = "load") -> Optional[int]:
    try:
        raw = kubectl("get", "deploy", deployment, "-n", namespace, "-o",
                      "jsonpath={.status.readyReplicas}")
        return int(raw) if raw.strip() else 0
    except Exception:
        return None


def _merge_slo(eng: dict, slo) -> dict:
    """Fold a pod's ``"slo"`` section into its engine entry — the shape
    :func:`slo_breached` and the scaler's :func:`~.scaler.role_burn`
    read, shared by the per-pod poll and the fleet-snapshot path."""
    if isinstance(slo, dict):
        eng["slo_breach"] = slo.get("breach", 0.0)
        for k, v in slo.items():
            if k.endswith("_burn"):
                eng[f"slo_{k}"] = v
    return eng


def fetch_fleet_stats(fleet_url: str, urls: Sequence[str],
                      timeout: float = 10.0
                      ) -> Optional[List[Optional[dict]]]:
    """ONE ``GET /fleet`` against cova instead of N per-pod polls: the
    fleet dump already carries every backend's full ``/stats`` body
    (``models``) plus the aggregated ``conformance`` verdicts — failover
    and scaling then decide from the SAME view of the fleet, instead of
    two pollers racing each other's snapshots.

    Returns entries in ``urls`` order (same contract as
    :func:`fetch_engine_stats`: one entry per url, None for backends the
    dump does not cover). Returns **None** — not a list — when the fleet
    endpoint itself is unreachable, so the caller can fall back to the
    legacy per-pod poll rung."""
    import httpx

    try:
        r = httpx.get(f"{fleet_url.rstrip('/')}/fleet", timeout=timeout)
        if r.status_code != 200:
            return None
        snap = r.json()
        models = snap.get("models") or {}
        by_url: Dict[str, dict] = {}
        for name, u in (snap.get("urls") or {}).items():
            body = models.get(name)
            if not isinstance(body, dict) or "error" in body:
                continue
            eng = body.get("engine")
            if isinstance(eng, dict):
                by_url[str(u).rstrip("/")] = _merge_slo(
                    dict(eng), body.get("slo"))
        return [by_url.get(u.rstrip("/")) for u in urls]
    except Exception:
        log.warning("fleet snapshot poll failed — falling back to "
                    "per-pod stats", exc_info=True)
        return None


def fetch_stats(urls: Sequence[str], fleet_url: str = "",
                timeout: float = 5.0) -> List[Optional[dict]]:
    """The deduped stats path: prefer the cova ``/fleet`` snapshot when a
    fleet URL is configured, degrade to the legacy per-pod poll when the
    snapshot is unavailable — one fleet view, with the old rung kept as
    the fallback."""
    if fleet_url:
        got = fetch_fleet_stats(fleet_url, urls)
        if got is not None:
            return got
    return fetch_engine_stats(urls, timeout=timeout)


def fetch_engine_stats(urls: Sequence[str],
                       timeout: float = 5.0) -> List[Optional[dict]]:
    """Poll each serving pod's ``/stats`` for its engine telemetry snapshot
    (``serve.app`` exposes the obs.steploop snapshot under ``"engine"``).
    Returns ONE entry per url: unreachable pods and engine-less services
    yield ``None`` — which :func:`is_overloaded` reads as healthy — so the
    overload-majority denominator in :func:`decide` stays the fleet size.
    (Dropping them instead would let a single hot pod constitute a "strict
    majority" during a rolling restart.)

    The pod's ``"slo"`` section (obs.slo burn-rate engine) is merged into
    the entry as ``slo_breach`` / ``slo_ttft_fast_burn`` etc., so the
    latency-driven failover trigger in :func:`decide` rides the same poll.
    """
    import httpx

    out: List[Optional[dict]] = []
    for u in urls:
        eng = None
        try:
            r = httpx.get(f"{u.rstrip('/')}/stats", timeout=timeout)
            body = r.json()
            got = body.get("engine")
            if isinstance(got, dict):
                eng = _merge_slo(dict(got), body.get("slo"))
        except Exception:
            log.debug("stats poll failed for %s", u, exc_info=True)
        out.append(eng)
    return out


def apply_mode(mode: str, manifest_dir: str, app: str) -> None:
    """Apply the ingress + scaledobjects for the target mode (the
    reference's kubectl-apply pair, ``capacity-checker-deploy.yaml:30-36``)."""
    kubectl("apply", "-f", f"{manifest_dir}/ingress/{app}-{mode}-routing-ing.yaml")
    kubectl("apply", "-f",
            f"{manifest_dir}/scaledobjects/{app}-scaledobject-{mode}-routing.yaml")


def main_loop(app: str = "sd21", manifest_dir: str = "/deploy",
              nodepools: Sequence[str] = ("tpu", "v5e"),
              load_deploy: str = "load", interval_s: int = 300,
              stats_urls: Sequence[str] = (),
              fleet_url: str = "") -> None:
    state = ControllerState()
    consecutive_failures = 0
    start_metrics_exporter()
    while True:
        try:
            action = decide(state, fetch_events(), fetch_load_ready(load_deploy),
                            nodepool_substrings=nodepools,
                            engine_stats=(fetch_stats(stats_urls,
                                                      fleet_url=fleet_url)
                                          if stats_urls else None))
            if action in ("failover", "fallback"):
                mode = "equal" if action == "failover" else "weighted"
                log.warning("%s -> applying %s routing (%s)", action, mode,
                            state.last_trigger)
                apply_mode(mode, manifest_dir, app)
                commit(state, action)  # only after the apply succeeded
            else:
                log.info("hold (mode=%s)", state.mode)
            consecutive_failures = 0
            time.sleep(interval_s)
        except Exception:
            consecutive_failures += 1
            count_controller_error()
            # retry fast at first (a transient blip recovers in seconds),
            # doubling up to the normal poll interval — never slower than
            # the healthy cadence, never a silent 5-minute crash loop
            pause = min(interval_s,
                        failure_backoff_s(consecutive_failures,
                                          cap_s=interval_s))
            log.exception(
                "capacity-checker iteration failed (%d consecutive, "
                "%d total) — retrying in %.0fs", consecutive_failures,
                controller_errors_total(), pause)
            time.sleep(pause)


if __name__ == "__main__":
    from ..obs.util import env_int, env_str

    logging.basicConfig(level="INFO")
    main_loop(
        app=env_str("APP", "sd21"),
        manifest_dir=env_str("MANIFEST_DIR", "/deploy"),
        nodepools=tuple(env_str("NODEPOOLS", "tpu,v5e").split(",")),
        load_deploy=env_str("LOAD_DEPLOY", "load"),
        interval_s=env_int("INTERVAL_S", 300),
        # comma-separated pod /stats base URLs: enables the engine-overload
        # failover trigger (queue depth / KV pressure from obs telemetry)
        stats_urls=tuple(u for u in
                         env_str("STATS_URLS").split(",") if u),
        # cova base URL: ONE /fleet snapshot replaces the per-pod polls
        # (failover and scaling decide from the same fleet view); the
        # per-pod rung stays as the fallback when cova is down
        fleet_url=env_str("FLEET_URL", ""),
    )
