"""The system under test: the ``vllm`` unit, booted as a pod boots it.

One process holds the chip, so the server runs in this process on a
background thread (``serve.__main__.boot``, ``create_app``, the in-repo HTTP
server — what ``chip_smoke.py`` does) and the load generator reaches it over
loopback HTTP. From the program the benchmark takes the served endpoints,
its counters (``StepTelemetry``) and, for the reference check, the engine's
parameter leaves.

One substitution is made, and said here: the geometry tier serves a BYTE
tokenizer whose ``decode`` drops every id above 258, so a model with a
32768-word vocabulary streams almost nothing and the client could not see a
token arrive. The benchmark gives the unit a tokenizer that encodes bytes
the same way and renders EVERY id as one ASCII letter, as a real vocabulary
renders every id as text. Prompts, token counts and device work are
unchanged.
"""

from __future__ import annotations

import json
import os
import sys
import threading
import time
import urllib.error
import urllib.request
from typing import Any, Dict, List, Tuple

COMPILE_EVENT = "/jax/core/compile/backend_compile_duration"


class NoAccelerator(RuntimeError):
    pass


def http_json(base: str, method: str, path: str, body=None,
              timeout: float = 300.0) -> Tuple[int, Any]:
    req = urllib.request.Request(
        base + path, method=method,
        data=None if body is None else json.dumps(body).encode(),
        headers={"content-type": "application/json",
                 "connection": "close"})
    try:
        with urllib.request.urlopen(req, timeout=timeout) as r:
            return r.status, json.loads(r.read() or b"null")
    except urllib.error.HTTPError as e:
        return e.code, e.read().decode("utf-8", "replace")


def visible_tokenizer(byte_tokenizer):
    """A tokenizer that encodes like ``byte_tokenizer`` and decodes every id
    to one letter."""

    class Visible(type(byte_tokenizer)):
        def decode(self, ids) -> str:
            return "".join(chr(97 + int(i) % 26) for i in ids)

    return Visible()


class SystemUnderTest:
    def __init__(self, config: Dict[str, Any], harness: Dict[str, Any],
                 seed: int, out_dir: str, dry_run: bool):
        self.config, self.harness = config, harness
        self.seed, self.out_dir, self.dry_run = int(seed), out_dir, dry_run
        self.compiles: List[Tuple[float, str, float]] = []  # (t, name, secs)
        self.base = ""
        self.split: Dict[str, float] = {}
        self._stopped = threading.Event()

    # -- boot ---------------------------------------------------------------

    def devices(self):
        """The devices the cell runs on; refuses anything but a TPU with the
        chips asked for (a dry run is the CPU, and says so)."""
        import jax

        from scalable_hw_agnostic_inference_tpu.core.device import (
            apply_platform,
        )

        apply_platform("cpu" if self.dry_run else "tpu")
        devs = jax.devices()
        want = int(self.config["chips"])
        if not self.dry_run:
            if devs[0].platform != "tpu":
                raise NoAccelerator(
                    f"the JAX backend is {devs[0].platform!r}, not a TPU: "
                    f"the benchmark measures nothing off the chip")
            if len(devs) < want:
                raise NoAccelerator(
                    f"the cell asks for {want} chips, JAX shows {len(devs)}")
        return devs

    def start(self) -> None:
        import jax
        import yaml

        from scalable_hw_agnostic_inference_tpu.serve.__main__ import boot
        from scalable_hw_agnostic_inference_tpu.serve.app import create_app
        from scalable_hw_agnostic_inference_tpu.serve.httpd import Server
        from scalable_hw_agnostic_inference_tpu.serve.metrics import (
            MetricsPublisher,
        )
        from scalable_hw_agnostic_inference_tpu.utils.env import ServeConfig

        t_boot = time.monotonic()
        jax.monitoring.register_event_duration_secs_listener(self._on_event)
        eng = dict(self.config["engine"])
        tp = int(eng.get("tensor_parallel_size", 1))
        if self.dry_run:
            tp = eng["tensor_parallel_size"] = min(
                tp, len(jax.devices()),
                int(self.harness["dry_run"]["tensor_parallel_size_max"]))
        model_id = (self.harness["dry_run"]["model"] if self.dry_run
                    else self.config["model_id"])
        quant = eng.get("quantization") or ""
        os.makedirs(self.out_dir, exist_ok=True)
        vllm_config = os.path.join(self.out_dir, "vllm_config.yaml")
        with open(vllm_config, "w") as f:
            yaml.safe_dump({**eng, "model": model_id,
                            "seed": self.seed % (2 ** 31)}, f)
        self.cfg = ServeConfig(
            app="benchmark", device="cpu" if self.dry_run else "tpu",
            model_id=model_id, quantization=quant, vllm_config=vllm_config,
            max_new_tokens=int(eng["max_new_tokens"]),
            artifact_root=os.path.join(self.out_dir, "artifacts"),
            seed=self.seed % (2 ** 32))
        self.service, _ = boot("vllm", self.cfg)
        self.cache_dir = jax.config.jax_compilation_cache_dir
        self.cache_entries_before = self._cache_entries()
        # the pod's per-request metric lines go to stderr: stdout is ours
        self.app = create_app(self.cfg, self.service, publisher=(
            MetricsPublisher(self.cfg.app, self.cfg.nodepool,
                             self.cfg.pod_name, stream=sys.stderr)))
        self.server = Server(self.app, host="127.0.0.1", port=0)
        self.split["boot_s"] = time.monotonic() - t_boot
        t_load = time.monotonic()
        self.host, self.port = self.server.start_background()
        self.base = f"http://{self.host}:{self.port}"
        self._wait_ready()
        self.t_ready = time.monotonic()
        self.split["load_and_warm_s"] = time.monotonic() - t_load
        self.split["xla_compile_s_until_ready"] = sum(
            s for _, _, s in self.compiles)
        self.split["xla_compiles_until_ready"] = len(self.compiles)
        self.service.tokenizer = visible_tokenizer(self.service.tokenizer)

    def _on_event(self, event, secs, fun_name="?", **kw) -> None:
        if event == COMPILE_EVENT:
            self.compiles.append((time.monotonic(), fun_name, secs))

    def _cache_entries(self) -> int:
        d = self.cache_dir
        return len(os.listdir(d)) if d and os.path.isdir(d) else 0

    def cache_entries_added(self) -> int:
        return self._cache_entries() - self.cache_entries_before

    def _wait_ready(self) -> None:
        deadline = time.monotonic() + float(self.harness["ready_timeout_s"])
        while True:
            status, raw = http_json(self.base, "GET", "/readiness", timeout=30)
            if status == 200:
                return
            if status != 503:
                raise RuntimeError(f"/readiness -> {status}: {raw!r:.2000}")
            if time.monotonic() > deadline:
                raise RuntimeError("not ready in time")
            time.sleep(0.5)

    # -- what the run reads -------------------------------------------------

    def generate(self, prompt: str, n_new: int) -> Dict[str, Any]:
        status, out = http_json(self.base, "POST", "/generate", {
            "prompt": prompt, "temperature": 0.0, "max_new_tokens": n_new,
            "logprobs": 5})
        if status != 200:
            raise RuntimeError(f"/generate -> {status}: {out!r:.500}")
        return out

    @property
    def params(self) -> Dict[str, Any]:
        return self.service._engine.params["params"]

    @property
    def telemetry(self):
        return self.service.engine_telemetry()

    def counters(self) -> Dict[str, Any]:
        """The program's counters, as ``/stats`` shows them, at one
        instant."""
        tele = self.telemetry
        status, stats = http_json(self.base, "GET", "/stats", timeout=30)
        return {"t": time.monotonic(), "engine": tele.snapshot(),
                "histograms": tele.histograms(),
                "shed": (stats.get("shed", {}) if status == 200 else {})}

    def compile_s_between(self, a: float, b: float) -> float:
        return sum(s for t, _, s in self.compiles if a <= t < b)

    def memory_peak_bytes(self) -> int:
        import jax

        peaks = [(d.memory_stats() or {}).get("peak_bytes_in_use", 0)
                 for d in jax.local_devices()]
        return int(max(peaks)) if peaks else 0

    # -- stop ---------------------------------------------------------------

    def stop(self) -> None:
        """The SIGTERM path: drain the engine loop, then stop the server."""
        if not getattr(self, "app", None):
            return
        self.app.state["begin_drain"](
            on_done=lambda: (self.server.request_shutdown(),
                             self._stopped.set()))
        self._stopped.wait(float(self.harness["drain_wait_s"]))
        thread = getattr(self.server, "_thread", None)
        if thread is not None:
            thread.join(10.0)
