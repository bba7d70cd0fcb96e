"""``chip_smoke.py``: the dry run goes end to end on the CPU, the plain form
refuses a CPU backend, and a failed phase cannot leave exit code 0."""

import importlib.util
import json
import os
import subprocess
import sys

import pytest

ROOT = os.path.abspath(os.path.join(os.path.dirname(__file__), os.pardir))


def _smoke(*args, **env):
    return subprocess.run(
        [sys.executable, os.path.join(ROOT, "chip_smoke.py"), *args],
        capture_output=True, text=True, timeout=600, cwd=ROOT,
        env={**os.environ, "JAX_PLATFORMS": "cpu", **env})


def test_dry_run_serves_tiny_end_to_end_and_says_it_was_a_dry_run(tmp_path):
    # a cache directory from the environment, not yet created: the compile
    # cache lands there (test_startup.py covers the checkout default)
    cache = tmp_path / "cc"
    r = _smoke("--dry-run", JAX_COMPILATION_CACHE_DIR=str(cache))
    assert r.returncode == 0, r.stderr[-3000:]
    lines = r.stdout.strip().splitlines()
    assert lines[0].startswith("chip_smoke: jax ") and "platform=cpu" in lines[0]
    result = json.loads(lines[-1])
    assert result["ok"] is True and result["dry_run"] is True
    assert result["device"]["platform"] == "cpu"
    report = json.loads(lines[-2][len("report "):])
    assert report["model_id"] == "tiny"
    assert report["cache_dir"] == str(cache)
    assert report["cache_entries_before"] == 0
    assert report["cache_entries_after"] == len(list(cache.iterdir())) > 0
    assert report["widest_decode_batch"] == 4
    assert len(report["kernel_max_abs_err"]) == 10  # flash x2, pool x8


def test_plain_form_refuses_a_cpu_backend():
    r = _smoke()
    assert r.returncode != 0
    assert '"ok"' not in r.stdout and "report" not in r.stdout
    assert "DEVICE=tpu requested but the JAX backend is 'cpu'" in r.stderr


def _load_smoke():
    spec = importlib.util.spec_from_file_location(
        "chip_smoke", os.path.join(ROOT, "chip_smoke.py"))
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def test_a_failed_load_fails_the_run_at_once():
    """``/readiness`` 500 ends the run with the load error instead of being
    polled until the ready timeout: the server keeps a process whose load
    failed alive, on purpose."""
    from scalable_hw_agnostic_inference_tpu.serve.app import (
        ModelService,
        create_app,
    )
    from scalable_hw_agnostic_inference_tpu.serve.httpd import Server
    from scalable_hw_agnostic_inference_tpu.utils.env import ServeConfig

    class Broken(ModelService):
        def load(self):
            raise RuntimeError("no weights here")

    smoke = _load_smoke()
    cfg = ServeConfig(app="broken", device="cpu")
    server = Server(create_app(cfg, Broken(cfg)), host="127.0.0.1", port=0)
    host, port = server.start_background()
    try:
        with pytest.raises(SystemExit) as e:
            smoke.wait_ready(f"http://{host}:{port}")
    finally:
        server.request_shutdown()
    assert "/readiness -> 500" in str(e.value)
    assert "no weights here" in str(e.value)


def test_a_disagreeing_kernel_fails_the_run():
    """No phase is wrapped in a try/except: a failed check is a SystemExit
    with a message, which is exit code 1."""
    import dataclasses

    smoke = _load_smoke()
    good = smoke.kernel_cases(dry_run=True, tp=1)[0]
    bad = dataclasses.replace(
        good, name="off-by-one", oracle=lambda *a: good.oracle(*a) + 1.0)
    with pytest.raises(SystemExit) as e:
        smoke.kernel_phase([good, bad], interpret=True)
    assert e.value.code != 0 and "off-by-one" in str(e.value)
    assert good.name not in str(e.value)
