import io
import json

import pytest

from scalable_hw_agnostic_inference_tpu.utils.env import ServeConfig
from scalable_hw_agnostic_inference_tpu.serve.latency import (
    LatencyCollector,
    run_benchmark,
)
from scalable_hw_agnostic_inference_tpu.serve.metrics import MetricsPublisher


class TestServeConfig:
    def test_defaults(self):
        cfg = ServeConfig()
        assert cfg.device == "tpu"
        assert cfg.port == 8000

    def test_from_env(self, monkeypatch):
        monkeypatch.setenv("APP", "sd21")
        monkeypatch.setenv("NODEPOOL", "tpu-v5e")
        monkeypatch.setenv("DEVICE", "cpu")
        monkeypatch.setenv("HEIGHT", "768")
        monkeypatch.setenv("GUIDANCE_SCALE", "5.0")
        cfg = ServeConfig.from_env()
        assert cfg.app == "sd21"
        assert cfg.nodepool == "tpu-v5e"
        assert cfg.device == "cpu"
        assert cfg.height == 768
        assert cfg.guidance_scale == 5.0

    def test_bad_device_rejected(self, monkeypatch):
        monkeypatch.setenv("DEVICE", "cuda")
        with pytest.raises(ValueError):
            ServeConfig.from_env()

    def test_describe_redacts_token(self):
        cfg = ServeConfig(hf_token="secret")
        assert cfg.describe()["hf_token"] == "***"


class TestLatencyCollector:
    def test_reservoir_is_uniform_over_the_stream(self):
        """Algorithm R keeps every observation with equal probability: after
        a long stream, the reservoir must cover the WHOLE stream roughly
        uniformly — not just the most recent max_samples (the old
        ``total % max_samples`` overwrite was a sliding window: nothing
        older than one reservoir length could survive)."""
        c = LatencyCollector(max_samples=500, seed=7)
        n = 5000
        for v in range(n):
            c.record(float(v))
        assert c.count == n and len(c._samples) == 500
        # early observations survive (impossible under round-robin: it kept
        # exactly the last 500 values, i.e. nothing below 4500)
        assert min(c._samples) < 1000
        # per-decile occupancy close to uniform (expected 50 per decile)
        deciles = [0] * 10
        for v in c._samples:
            deciles[int(v) * 10 // n] += 1
        assert all(20 <= d <= 90 for d in deciles), deciles
        # deterministic given the seed (private RNG stream)
        c2 = LatencyCollector(max_samples=500, seed=7)
        for v in range(n):
            c2.record(float(v))
        assert c._samples == c2._samples

    def test_percentiles(self):
        c = LatencyCollector()
        for v in range(1, 101):
            c.record(v / 100.0)
        assert c.count == 100
        assert c.percentile(0) == pytest.approx(0.01)
        assert c.percentile(100) == pytest.approx(1.0)
        assert c.percentile(50) == pytest.approx(0.505, abs=0.01)
        rep = c.report()
        assert set(rep) == {"p0", "p50", "p90", "p95", "p99", "p100"}
        assert rep["p90"] <= rep["p95"] <= rep["p99"]

    def test_empty(self):
        c = LatencyCollector()
        assert c.percentile(50) == 0.0

    def test_reservoir_bound(self):
        c = LatencyCollector(max_samples=10)
        for v in range(1000):
            c.record(float(v))
        assert c.count == 1000
        assert len(c._samples) == 10

    def test_benchmark(self):
        calls = []
        rep = run_benchmark(lambda: calls.append(1), n_runs=5)
        assert rep.n_runs == 5 and len(calls) == 5
        assert rep.throughput_rps > 0
        d = rep.to_dict()
        assert "p50" in d and d["n_runs"] == 5


class TestMetrics:
    def test_publish_json_lines(self):
        buf = io.StringIO()
        pub = MetricsPublisher("sd21", "tpu-v5e", pod_name="p0", stream=buf)
        pub.publish(0.25)
        pub.publish(0.5, count=3)
        assert pub.served == 4
        lines = [json.loads(l) for l in buf.getvalue().strip().splitlines()]
        assert lines[0]["data"]["sd21-counter"] == 1
        assert lines[0]["data"]["tpu-v5e"] == 1
        assert lines[1]["data"]["sd21-counter"] == 3
        assert lines[0]["ns"] == "hw-agnostic-infer"
        assert lines[0]["pod"] == "p0"

    def test_count_shed_json_data_is_numeric(self):
        """The shed reason rides in the metric NAME — "data" is a
        name -> number map for the CloudWatch-style consumer, so a string
        "reason" entry would break its float() ingestion (and collapse
        per-reason counts)."""
        buf = io.StringIO()
        pub = MetricsPublisher("sd21", "np", pod_name="p0", stream=buf)
        pub.count_shed("queue_depth")
        pub.count_shed("draining")
        lines = [json.loads(l) for l in buf.getvalue().strip().splitlines()]
        assert lines[0]["data"] == {"sd21-shed-queue_depth": 1}
        assert lines[1]["data"] == {"sd21-shed-draining": 1}
        for line in lines:
            assert all(isinstance(v, (int, float))
                       for v in line["data"].values())

    def test_prometheus_counter(self):
        pub = MetricsPublisher("sd21", "np", emit_json=False)
        pub.publish(0.1)
        if pub.registry is not None:
            val = pub.registry.get_sample_value(
                "shai_requests_total",
                {"app": "sd21", "nodepool": "np", "pod": ""},
            )
            assert val == 1.0

    def test_prometheus_absent_fallback_path(self, monkeypatch):
        """Minimal envs have no prometheus_client: every publisher method
        must still work through the JSON-lines path (previously only the
        happy path was exercised — a pod without the package would have
        found any AttributeError here in production)."""
        from scalable_hw_agnostic_inference_tpu.serve import metrics as m

        monkeypatch.setattr(m, "_HAVE_PROM", False)
        buf = io.StringIO()
        pub = MetricsPublisher("sd21", "np", pod_name="p0", stream=buf)
        assert pub.registry is None
        pub.publish(0.25)
        pub.publish_spec(drafted=10, accepted=7, committed=9)
        assert pub.attach_engine_telemetry(lambda: None) is False
        pub.publish_engine({"steps": 3, "waiting": 1.0, "kind": "decode"})
        pub.publish_engine({"steps": 3, "waiting": 2.0})  # deduped: same step
        lines = [json.loads(l) for l in buf.getvalue().strip().splitlines()]
        assert lines[0]["data"]["sd21-counter"] == 1
        assert lines[1]["data"]["sd21-spec-acceptance"] == 0.7
        engine_lines = [l for l in lines
                        if "sd21-engine-steps" in l["data"]]
        assert len(engine_lines) == 1  # the duplicate snapshot was dropped
        assert engine_lines[0]["data"]["sd21-engine-waiting"] == 1.0
        assert pub.served == 1

    def test_publish_engine_object_form_defers_snapshot(self):
        """The hot path hands publish_engine the live telemetry object; a
        deduped call (step count unchanged since the last line) must cost
        one int compare — no snapshot dict built and thrown away."""

        class Tele:
            steps = 5
            snapshots = 0

            def snapshot(self):
                self.snapshots += 1
                return {"steps": self.steps, "waiting": 4.0}

        buf = io.StringIO()
        pub = MetricsPublisher("sd21", "np", pod_name="p0", stream=buf)
        tele = Tele()
        pub.publish_engine(tele)
        pub.publish_engine(tele)   # deduped: snapshot() must not run again
        assert tele.snapshots == 1
        tele.steps = 6
        pub.publish_engine(tele)
        assert tele.snapshots == 2
        lines = [json.loads(l) for l in buf.getvalue().strip().splitlines()]
        assert len(lines) == 2
        assert lines[0]["data"]["sd21-engine-waiting"] == 4.0

    @pytest.mark.asyncio
    async def test_metrics_endpoint_404_without_prometheus(self, monkeypatch):
        """/metrics must 404 (not 500) when prometheus_client is absent."""
        import httpx

        from scalable_hw_agnostic_inference_tpu.serve import metrics as m
        from scalable_hw_agnostic_inference_tpu.serve.app import create_app

        from test_serve_http import EchoService, make_cfg, wait_ready

        monkeypatch.setattr(m, "_HAVE_PROM", False)
        cfg = make_cfg()
        pub = MetricsPublisher(cfg.app, cfg.nodepool, emit_json=False)
        app = create_app(cfg, EchoService(cfg), publisher=pub)
        transport = httpx.ASGITransport(app=app)
        async with httpx.AsyncClient(transport=transport,
                                     base_url="http://t") as c:
            await wait_ready(c)
            r = await c.get("/metrics")
            assert r.status_code == 404
            # the rest of the surface is unaffected
            r = await c.post("/predict", json={"text": "hi"})
            assert r.status_code == 200
