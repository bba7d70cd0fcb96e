"""The ten metrics of PR 56: the share of a window in which the pod did not
run, by what stopped it (``obs/stops.py``: garbage collections, the machine,
the interpreter lock kept) and the seconds its stalled steps ran over the
median (``obs/steploop.py`` ``stall``). Ten data files over the reader that
was there, ``counter_rate``: a counter's change over the window's seconds,
x 100. On a program without the groups (the parent commit) each reader
returns nothing.

The lists of ``BENCHMARK.json`` are held by what this PR knew: a prefix, a
slice at a known place, the order of two names; a later PR appends.
"""

import json
import os
import subprocess
import sys

import pytest

from benchmark.spec import Spec
from scalable_hw_agnostic_inference_tpu.obs.steploop import StepTelemetry
from scalable_hw_agnostic_inference_tpu.obs.stops import ProcessStops

SPEC = Spec()
#: the ONE-CHIP cells that report ``out_tok_per_s`` and the two open-loop
#: cells, in the order the accepted ``.serve`` and ``.rate`` lists had them
#: when these came. The four-chip cell is left off: with it on five more
#: lists the rehearsal's appended copy of ``BENCHMARK.json`` passes the
#: contract's 64 KiB (``test_benchmark_append.py``; PERF.md section 7)
SERVE = ["mistral-7b-int8.decode-sat",
         "trinity-mini-bf16.decode-sat-4k",
         "kanana-2-30b-a3b-bf16.decode-sat-8k",
         "nemotron-3-nano-30b-a3b-bf16-ep2.decode-sat-1k",
         "lfm2-24b-a2b-bf16.decode-sat-turns"]
RATE = ["mistral-7b-int8.prefill-rate",
        "kimi-linear-48b-a3b-bf16-ep2.prefill-rate-16k"]
HTTP, ADM = "HTTP and lanes", "admission and scheduler"
#: metric stem -> (counter, layer, what ``.serve`` moves, what ``.rate``)
STEMS = {
    "gc_pause_share": ("gc.pause_s", HTTP, "out_tok_per_s", "gap_p95_ms"),
    "gc_full_pause_share": ("gc.full_pause_s", HTTP, "out_tok_per_s",
                            "gap_p95_ms"),
    "process_frozen_share": ("stops.frozen_s", HTTP, "out_tok_per_s",
                             "ttft_p90_ms"),
    "process_starved_share": ("stops.starved_s", HTTP, "out_tok_per_s",
                              "ttft_p90_ms"),
    "stalled_step_share": ("stall.excess_s", ADM, "out_tok_per_s",
                           "gap_p95_ms"),
}
NEW = [f"{stem}{suffix}" for stem in STEMS for suffix in (".serve", ".rate")]


def expected(name):
    stem, suffix = name.rsplit(".", 1)
    counter, layer, serve, rate = STEMS[stem]
    return (counter, layer, serve if suffix == "serve" else rate,
            SERVE if suffix == "serve" else RATE)


@pytest.mark.parametrize("name", NEW)
def test_a_new_entry_keeps_the_rules(name):
    counter, layer, moves, cells = expected(name)
    entry, mf = SPEC.metric_entry(name), SPEC.layer_metric(name)
    assert mf["reader"] == {"kind": "counter_rate", "counter": counter,
                            "scale": 100.0}
    for k, v in (("unit", "%"), ("better", "lower"), ("layer", layer),
                 ("source", "program_counter"), ("moves", moves)):
        assert entry[k] == mf[k] == v, k
    assert set(entry) == {"name", "unit", "better", "source", "layer",
                          "moves", "workloads"}
    # the cells this PR listed come first, in the accepted lists' order
    assert entry["workloads"][:len(cells)] == cells
    # and every cell on the list reports the end-to-end metric it moves
    for cell in entry["workloads"]:
        assert moves in SPEC.cell_end_to_end(cell), cell
        assert name in SPEC.cell_layer_metrics(cell)


def test_the_new_entries_are_appended_and_the_benchmark_is_whole():
    assert SPEC.problems() == []
    names = [m["name"] for m in SPEC.bench["per_layer"]]
    first = names.index(NEW[0])
    assert names[first:first + len(NEW)] == NEW
    # behind the last entry of the PR before this one
    assert names[first - 1] == "first_tokens_fed_share.sat"
    assert os.path.exists(os.path.join(SPEC.dir, "readers",
                                       "counter_rate.py"))


def _ctx(before, after, seconds=30.0):
    return {"before": {"t": 100.0, "engine": before},
            "after": {"t": 100.0 + seconds, "engine": after}}


def _read(name, ctx):
    params = SPEC.layer_metric(name)["reader"]
    return SPEC.reader(params["kind"]).read(ctx, params)


@pytest.mark.parametrize("name", NEW)
def test_on_a_snapshot_without_the_groups_the_reader_returns_nothing(name):
    """The parent commit's snapshot has none of ``gc``, ``stops``,
    ``stall``; neither has an engine's with no app around it the first
    two."""
    parent = {"steps": 10, "tokens_committed": 100, "stream": {}}
    assert _read(name, _ctx(parent, dict(parent, steps=20))) is None
    bare = StepTelemetry().snapshot()
    assert not {"gc", "stops"} & set(bare)
    want_none = not name.startswith("stalled_step_share")
    assert (_read(name, _ctx(bare, bare)) is None) == want_none


def test_the_readers_take_the_share_of_the_window_from_the_counters():
    """A 0.21 s full collection, a 1.5 s freeze and a stalled step in a 30 s
    window, counted by the program's own objects."""
    t = {"now": 50.0, "cpu": 1.0}
    tele = StepTelemetry(total_blocks=10, max_steps=4)
    stops = tele.stops = ProcessStops(clock=lambda: t["now"],
                                      cpu_clock=lambda: t["cpu"])
    stops.started = True
    for _ in range(4):
        tele.record_step(kind="decode", duration_s=0.015, n_running=1,
                         n_waiting=0, n_chunking=0, blocks_free=5)
    before = tele.snapshot()
    gen2 = {"generation": 2, "collected": 3, "uncollectable": 0}
    gen0 = {"generation": 0, "collected": 1, "uncollectable": 0}
    for info, dt in ((gen2, 0.21), (gen0, 0.003)):
        stops._on_gc("start", info)
        t["now"] += dt
        stops._on_gc("stop", info)
    due, cpu0 = t["now"] + 0.02, t["cpu"]
    t["now"] = due + 1.5
    stops.beat(due, cpu0)
    tele.record_step(kind="decode", duration_s=1.515, n_running=1,
                     n_waiting=0, n_chunking=0, blocks_free=5)
    ctx = _ctx(before, tele.snapshot())
    assert _read("gc_full_pause_share.rate", ctx) == pytest.approx(0.7)
    assert _read("gc_pause_share.serve", ctx) == pytest.approx(0.71)
    assert _read("process_frozen_share.serve", ctx) == pytest.approx(5.0)
    assert _read("process_starved_share.rate", ctx) == 0.0
    assert _read("stalled_step_share.serve", ctx) == pytest.approx(5.0)


@pytest.mark.parametrize("cell", ["lfm2-24b-a2b-bf16.decode-sat-turns",
                                  "mistral-7b-int8.prefill-rate"])
def test_a_traced_dry_run_prints_the_five_new_metrics(cell, tmp_path):
    """One saturated and one open-loop cell, tiny, on the CPU, booted
    through ``create_app`` as a pod is: the app started the instrument, so
    all five of the cell's new metrics are numbers in its line."""
    p = subprocess.run(
        [sys.executable, os.path.join(SPEC.root, "benchmark", "run.py"),
         "--workload", cell, "--seed", "3000000056", "--seconds", "2",
         "--trace", "1", "--dry-run", "--out", str(tmp_path / "out")],
        env={**os.environ, "JAX_PLATFORMS": "cpu"}, cwd=SPEC.root,
        capture_output=True, text=True, timeout=600)
    assert p.returncode == 0, p.stderr[-3000:]
    result = json.loads(p.stdout.strip().splitlines()[-1])
    metrics = result["metrics"]
    suffix = ".serve" if cell in SERVE else ".rate"
    for stem in STEMS:
        assert metrics[stem + suffix]["unit"] == "%"
        assert metrics[stem + suffix]["value"] >= 0, stem
    other = ".rate" if suffix == ".serve" else ".serve"
    assert not {stem + other for stem in STEMS} & set(metrics)
    assert (metrics["gc_pause_share" + suffix]["value"]
            >= metrics["gc_full_pause_share" + suffix]["value"])
    assert "left out" not in p.stderr
    assert result["correct"] is True and result["failed"] == 0
