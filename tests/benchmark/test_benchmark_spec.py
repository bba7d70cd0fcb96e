"""The benchmark's files against the contract, and new cells as new files."""

import json
import os
import re
import shutil

import pytest

from benchmark import spec as spec_mod
from benchmark.spec import Spec

SPEC = Spec()
BENCH = SPEC.bench
NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.\-]{0,63}$")
UNIT = re.compile(r"^[A-Za-z0-9_/%.\-]{1,16}$")
WORKLOADS = [w["name"] for w in BENCH["workloads"]]
METRICS = BENCH["end_to_end"] + BENCH["per_layer"]


def test_the_files_keep_the_rules_among_themselves():
    assert SPEC.problems() == []


def test_benchmark_json_has_exactly_the_contracts_keys():
    assert set(BENCH) == {"command", "paths", "run_seconds", "configs",
                          "workloads", "end_to_end", "per_layer"}
    assert 1 <= BENCH["run_seconds"] <= 51
    assert os.path.getsize(os.path.join(SPEC.root, "BENCHMARK.json")) < 65536
    for c in BENCH["configs"]:
        assert set(c) == {"name", "source", "file", "reduced", "why"}
        assert c["reduced"] == []
    for w in BENCH["workloads"]:
        assert set(w) == {"name", "config", "traffic", "chips", "why"}
        assert w["chips"] in (1, 4)
    for m in BENCH["end_to_end"]:
        assert set(m) - {"workloads"} == {"name", "unit", "better", "bound",
                                          "source"}
        assert 0.01 <= m["bound"] <= 0.1
        assert m["source"] in ("host_clock", "device_trace")
    for m in BENCH["per_layer"]:
        assert set(m) - {"workloads"} == {"name", "unit", "better", "source",
                                          "layer", "moves"}
    four = sum(w["chips"] == 4 for w in BENCH["workloads"])
    assert four <= max(1, len(BENCH["workloads"]) // 4)


@pytest.mark.parametrize("m", METRICS, ids=lambda m: m["name"])
def test_metric_names_and_units_fit_the_allowed_characters(m):
    assert NAME.match(m["name"]) and UNIT.match(m["unit"])
    assert m["better"] in ("lower", "higher")
    if "roofline" in m["name"] or "mfu" in m["name"]:
        assert m["unit"] == "%"


@pytest.mark.parametrize("text", [w["why"] for w in BENCH["workloads"]]
                         + [c["why"] for c in BENCH["configs"]]
                         + [c["source"] for c in BENCH["configs"]]
                         + [m["layer"] for m in BENCH["per_layer"]])
def test_free_text_is_one_short_line(text):
    assert 1 <= len(text) <= 200 and "\n" not in text and "\t" not in text


@pytest.mark.parametrize("name", WORKLOADS)
def test_every_cells_files_exist_and_agree(name):
    w, cfg, mix = SPEC.cell(name)
    assert cfg["chips"] == w["chips"] and cfg["reduced"] == []
    assert cfg["source"].startswith("https://huggingface.co/mistralai/")
    e2e = SPEC.cell_end_to_end(name)
    assert "setup_s" in e2e and len(e2e) >= 2
    assert mix["why"] and mix["who"] and mix["assumed"]["lengths"]
    # which cells report a metric stands in BENCHMARK.json alone
    assert not {"end_to_end", "per_layer"} & (set(mix) | set(cfg))
    layer_metrics = SPEC.cell_layer_metrics(name)
    assert layer_metrics
    for metric in layer_metrics:
        mf = SPEC.layer_metric(metric)
        assert mf["moves"] in e2e, (metric, mf["moves"])
        assert hasattr(SPEC.reader(mf["reader"]["kind"]), "read")
    for path in BENCH["paths"]:
        assert os.path.isdir(os.path.join(SPEC.root, path))


def test_published_widths_are_the_sources_own():
    for c in BENCH["configs"]:
        cfg = SPEC.config(c["name"])
        assert (cfg["hidden_size"], cfg["intermediate_size"],
                cfg["num_hidden_layers"], cfg["num_attention_heads"],
                cfg["num_key_value_heads"], cfg["vocab_size"],
                cfg["head_dim"], cfg["rope_theta"], cfg["rms_norm_eps"]) == (
            4096, 14336, 32, 32, 8, 32768, 128, 1000000.0, 1e-05)
        assert cfg["engine"]["max_num_seqs"] == 8


def test_metrics_of_one_layer_spell_it_alike_and_peaks_name_their_source():
    layers = {m["layer"] for m in BENCH["per_layer"]}
    assert len({x.lower() for x in layers}) == len(layers)
    for kind, peak in SPEC.peaks.items():
        assert peak["source"] and peak["bf16_flops_per_s"] > 0
    with pytest.raises(spec_mod.SpecError):
        SPEC.peak("TPU v9 imaginary")


def test_files_under_paths_are_named_from_allowed_characters():
    ok = re.compile(r"^[A-Za-z0-9_.\-/]+$")
    for path in BENCH["paths"]:
        for d, dirs, files in os.walk(os.path.join(SPEC.root, path)):
            dirs[:] = [x for x in dirs if x != "__pycache__"]
            for f in files:
                rel = os.path.relpath(os.path.join(d, f), SPEC.root)
                assert ok.match(rel) and len(rel) <= 200, rel


def test_a_new_config_mix_metric_and_reader_kind_need_no_edit(tmp_path):
    """A later PR adds files and entries and edits no file that is there:
    a new cell of a new configuration and a new mix, and one more per-layer
    metric on a cell the benchmark already has."""
    root = tmp_path / "checkout"
    shutil.copytree(os.path.join(SPEC.root, "benchmark"), root / "benchmark",
                    ignore=shutil.ignore_patterns("__pycache__"))
    before = {p: p.read_bytes() for p in (root / "benchmark").rglob("*")
              if p.is_file()}
    bdir = root / "benchmark"
    cfg = SPEC.config("mistral-7b-int8")
    cfg.update(name="mistral-7b-int8-long")
    cfg["engine"] = {**cfg["engine"], "max_model_len": 4096}
    (bdir / "configs" / "mistral-7b-int8-long.json").write_text(
        json.dumps(cfg))
    (bdir / "readers" / "answer.py").write_text(
        "def read(ctx, params):\n    return params['value'] * 2\n")
    old_cell = "mistral-7b-int8.decode-sat"
    new_cell = "mistral-7b-int8-long.decode-long"
    added = {"answer.long": [new_cell], "answer.sat": [old_cell]}
    for name in added:
        (bdir / "layer_metrics" / f"{name}.json").write_text(json.dumps({
            "name": name, "layer": "device", "unit": "count",
            "better": "higher", "source": "program_counter",
            "moves": "out_tok_per_s",
            "reader": {"kind": "answer", "value": 21}}))
    mix = SPEC.traffic("decode-sat")
    mix.update(clients=16)
    (bdir / "traffic" / "decode-long.json").write_text(json.dumps(mix))
    bench = json.loads(json.dumps(BENCH))
    bench["configs"].append({
        "name": "mistral-7b-int8-long", "source": cfg["source"],
        "file": "benchmark/configs/mistral-7b-int8-long.json",
        "reduced": [], "why": "longer contexts"})
    bench["workloads"].append({
        "name": new_cell, "config": "mistral-7b-int8-long",
        "traffic": "decode-long", "chips": 1, "why": "test"})
    for name, cells in added.items():
        bench["per_layer"].append({
            "name": name, "unit": "count", "better": "higher",
            "source": "program_counter", "layer": "device",
            "moves": "out_tok_per_s", "workloads": cells})
    for m in bench["end_to_end"]:
        if m["name"] == "out_tok_per_s":
            m["workloads"].append(new_cell)
    (root / "BENCHMARK.json").write_text(json.dumps(bench))
    new = Spec(str(root))
    assert new.problems() == []
    w, c, m = new.cell(new_cell)
    assert c["engine"]["max_model_len"] == 4096 and m["clients"] == 16
    assert new.cell_end_to_end(new_cell) == ["out_tok_per_s", "setup_s"]
    # entries with no ``workloads`` key are every cell's
    assert new.cell_layer_metrics(new_cell) == [
        "xla_compile_s_setup", "cache_entries_added", "answer.long"]
    assert new.cell_layer_metrics(old_cell) == (
        SPEC.cell_layer_metrics(old_cell) + ["answer.sat"])
    assert "answer.sat" not in new.cell_layer_metrics(
        "mistral-7b-bf16-tp4.decode-sat")
    mf = new.layer_metric("answer.sat")
    assert new.reader(mf["reader"]["kind"]).read({}, mf["reader"]) == 42
    for p, raw in before.items():
        assert p.read_bytes() == raw, f"{p} was edited"


def _moves_nothing(bench):
    bench["per_layer"][0]["moves"] = "setup_s_of_nothing"
    return bench["per_layer"][0]["name"]


def _lists_no_workload(bench):
    bench["per_layer"][0]["workloads"] = ["mistral-7b-int8.no-such-mix"]
    return "which is no workload"


def _cell_without_layer_metrics(bench):
    bench["per_layer"] = [m for m in bench["per_layer"]
                          if "workloads" in m
                          and WORKLOADS[0] not in m["workloads"]]
    return f"{WORKLOADS[0]}: reports no per-layer metric"


def _metric_without_a_file(bench):
    bench["per_layer"].append({**bench["per_layer"][0], "name": "nowhere"})
    return "nowhere.json"


@pytest.mark.parametrize("breakage", [
    _moves_nothing, _lists_no_workload, _cell_without_layer_metrics,
    _metric_without_a_file], ids=lambda f: f.__name__.strip("_"))
def test_a_broken_cell_is_named(tmp_path, breakage):
    root = tmp_path / "checkout"
    shutil.copytree(os.path.join(SPEC.root, "benchmark"), root / "benchmark",
                    ignore=shutil.ignore_patterns("__pycache__"))
    bench = json.loads(json.dumps(BENCH))
    said = breakage(bench)
    (root / "BENCHMARK.json").write_text(json.dumps(bench))
    problems = Spec(str(root)).problems()
    assert any(said in p for p in problems), problems
