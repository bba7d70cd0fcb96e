"""The command itself: a dry run end to end on the CPU, and the refusal to
measure anything off a TPU."""

import json
import os
import subprocess
import sys

import pytest

from benchmark.spec import Spec

SPEC = Spec()
RUN = [sys.executable, os.path.join(SPEC.root, "benchmark", "run.py")]
#: the one dry run of ``_dry_runs`` that is traced, by name (it was the last
#: of them, and "the last" moves with every cell a later PR appends)
TRACED = "kimi-linear-48b-a3b-bf16-ep2.prefill-rate-16k"


def _run(args, tmp_path, **env):
    full_env = {**os.environ, "JAX_PLATFORMS": "cpu", **env}
    return subprocess.run(
        RUN + args + ["--out", str(tmp_path / "out")], env=full_env,
        cwd=SPEC.root, capture_output=True, text=True, timeout=600)


def _dry_runs():
    """The first cell of every configuration and of every traffic mix, in
    ``BENCHMARK.json``'s order, ``TRACED`` of them traced."""
    cells, configs, mixes = [], set(), set()
    for w in SPEC.bench["workloads"]:
        if w["config"] not in configs or w["traffic"] not in mixes:
            cells.append(w["name"])
        configs.add(w["config"])
        mixes.add(w["traffic"])
    return [(c, int(c == TRACED)) for c in cells]


@pytest.mark.parametrize("workload,trace", _dry_runs())
def test_dry_run_prints_the_contracts_last_line(workload, trace, tmp_path):
    p = _run(["--workload", workload, "--seed", "2147483659", "--seconds",
              "2", "--trace", str(trace), "--dry-run"], tmp_path)
    assert p.returncode == 0, p.stderr[-3000:]
    lines = p.stdout.strip().splitlines()
    result = json.loads(lines[-1])
    assert set(result) == {"correct", "attempted", "failed", "metrics",
                           "device"}
    assert result["device"]["platform"] == "cpu"
    assert set(result["device"]) == {"platform", "kind", "count",
                                     "memory_peak_bytes"}
    assert result["correct"] is True
    assert result["failed"] == 0 and result["attempted"] > 0
    if trace:
        # a CPU run reports no device number under a device metric's name
        for name in result["metrics"]:
            assert SPEC.metric_entry(name)["source"] != "device_trace"
        assert "cache_entries_added" in result["metrics"]
    else:
        assert list(result["metrics"]) == SPEC.cell_end_to_end(workload)
    for m in result["metrics"].values():
        assert set(m) == {"value", "unit"}
    earlier = [ln.split(" ", 1)[0] for ln in lines[:-1]]
    assert {"device", "reference", "run"} <= set(earlier)
    run = json.loads([ln for ln in lines if ln.startswith("run ")][0][4:])
    assert "eos_stops" in run and "setup_split" in run
    assert not os.path.exists(tmp_path / "out" / "failures.jsonl")


def test_without_dry_run_it_refuses_anything_but_a_tpu(tmp_path):
    p = _run(["--workload", SPEC.bench["workloads"][0]["name"], "--seed",
              "1", "--seconds", "1", "--trace", "0"], tmp_path)
    assert p.returncode != 0
    assert "not a TPU" in p.stderr
    assert not [ln for ln in p.stdout.splitlines() if ln.startswith("{")]


def test_an_unknown_workload_is_refused_before_anything_boots(tmp_path):
    p = _run(["--workload", "no-such.cell", "--seed", "1", "--seconds", "1",
              "--trace", "0", "--dry-run"], tmp_path)
    assert p.returncode != 0 and "no workload" in p.stderr
