"""The rule every file of ``tests/benchmark`` keeps, rehearsed (PR 44):

    What an accepted PR put into a list of ``BENCHMARK.json`` is still there,
    in its order, BEFORE anything newer; a later PR appends behind it. So a
    test holds a list by a prefix, a slice at known positions or the order of
    two names, never by its end, its length, its whole, or a loop over every
    cell or metric that is not cut to the names the test's own PR knew.

``configs``, ``workloads``, ``per_layer`` and each metric's own ``workloads``
(end-to-end and per-layer) are such lists. A later PR may add files and list
entries under ``BENCHMARK.json``'s ``paths`` and edit no file that is there,
this directory's tests among them: a test that pins a list's end fails in the
first PR that appends, which cannot mend it.

The rehearsal appends to a copy, as later PRs would, and runs the copied
tests of this directory against the copy. A test written here with such a pin
fails here, in the PR that writes it. What it leaves out of that run, by
name: itself, the tests with ``dry_run`` in their names (they spawn
``benchmark/run.py``; give a new one that word too), and two files whose
work is one boot or one schedule for every configuration or mix and that
compare nothing with a list (``test_benchmark_reference.py``,
``test_benchmark_traffic.py``): all of them run where they stand.
"""

import json
import os
import shutil
import subprocess
import sys

import pytest

from benchmark.spec import Spec

SPEC = Spec()
#: what the copy's run of this directory leaves out (the docstring says why)
LEFT_OUT_FILES = ["test_benchmark_append.py", "test_benchmark_reference.py",
                  "test_benchmark_traffic.py"]
LEFT_OUT_NAMES = "not dry_run"
COPIED_FROM = "kanana-2-30b-a3b-bf16"
CONFIG = "appended-a3b-bf16"
DECODE, RATE = CONFIG + ".decode-sat-8k", CONFIG + ".prefill-rate-16k"
METRIC = {"layer": "device", "unit": "count", "better": "higher",
          "source": "program_counter"}
READER = "def read(ctx, params):\n    return params['value'] * 2\n"


def _copy(root):
    """``BENCHMARK.json`` and both of its ``paths`` under ``root``, and the
    bytes of every file."""
    for path in SPEC.bench["paths"]:
        shutil.copytree(os.path.join(SPEC.root, path), root / path,
                        ignore=shutil.ignore_patterns("__pycache__"))
    shutil.copy(os.path.join(SPEC.root, "BENCHMARK.json"), root)
    return {p: p.read_bytes() for p in root.rglob("*") if p.is_file()}


def _join(bench, cell, holds):
    """``cell`` behind the cells of every metric's list that ``holds``."""
    for m in bench["end_to_end"] + bench["per_layer"]:
        if "workloads" in m and holds(m["workloads"]):
            m["workloads"].append(cell)


def append(root):
    """What later PRs do to the checkout at ``root``, by new files and list
    entries alone; returns the cells added, the accepted cell each of the
    ``-again`` ones stands beside, and the two metrics."""
    bdir = root / "benchmark"
    bench = json.loads((root / "BENCHMARK.json").read_text())
    accepted = list(bench["workloads"])
    serve, rate = ([w["name"] for w in accepted
                    if m in SPEC.cell_end_to_end(w["name"])]
                   for m in ("out_tok_per_s", "ttft_p90_ms"))

    # a configuration: its file and its pinned published keys under a new name
    cfg = {**SPEC.config(COPIED_FROM), "name": CONFIG}
    (bdir / "configs" / f"{CONFIG}.json").write_text(json.dumps(cfg))
    published = root / "tests" / "benchmark" / "data" / "published"
    shutil.copy(published / f"{COPIED_FROM}.json",
                published / f"{CONFIG}.json")
    entry = [c for c in bench["configs"] if c["name"] == COPIED_FROM][0]
    bench["configs"].append({**entry, "name": CONFIG,
                             "file": f"benchmark/configs/{CONFIG}.json"})
    # its decode cell joins what every saturated cell reports, its rate cell
    # what every rate cell reports, and both the set-up lists
    for cell, mix, with_all in ((DECODE, "decode-sat-8k", serve),
                                (RATE, "prefill-rate-16k", rate)):
        bench["workloads"].append({
            "name": cell, "config": CONFIG, "traffic": mix, "chips": 1,
            "why": "the rehearsal's"})
        _join(bench, cell, lambda cells: set(with_all) <= set(cells))

    # one more cell of every accepted configuration, under a new mix (as
    # ``mistral-7b-int8.chat-rate`` will come): it joins every list that
    # holds the accepted cell, the lists of one architecture's kernels too
    beside = {}
    for w in accepted:
        mix = w["traffic"] + "-again"
        (bdir / "traffic" / f"{mix}.json").write_text(json.dumps(
            SPEC.traffic(w["traffic"])))
        cell = f"{w['config']}.{mix}"
        bench["workloads"].append({**w, "name": cell, "traffic": mix})
        _join(bench, cell, lambda cells: w["name"] in cells)
        beside[cell] = w["name"]

    # two per-layer metrics over a reader kind of their own, at the END
    (bdir / "readers" / "appended_answer.py").write_text(READER)
    metrics = {"appended_answer.new": DECODE,
               "appended_answer.old": accepted[0]["name"]}
    for name, cell in metrics.items():
        e = {"name": name, **METRIC, "moves": "out_tok_per_s"}
        (bdir / "layer_metrics" / f"{name}.json").write_text(json.dumps(
            {**e, "reader": {"kind": "appended_answer", "value": 21}}))
        bench["per_layer"].append({**e, "workloads": [cell]})
    (root / "BENCHMARK.json").write_text(json.dumps(bench, indent=1))
    return {"serve": serve, "rate": rate, "beside": beside,
            "metrics": metrics}


@pytest.fixture(scope="module")
def appended(tmp_path_factory):
    root = tmp_path_factory.mktemp("appended") / "checkout"
    root.mkdir()
    before = _copy(root)
    return root, before, append(root)


def test_the_appended_copy_keeps_the_rules_and_no_file_is_edited(appended):
    root, before, added = appended
    new = Spec(str(root))
    assert new.problems() == []
    for p, raw in before.items():
        if p.name != "BENCHMARK.json":
            assert p.read_bytes() == raw, f"{p} was edited"
    assert len({p for p in root.rglob("*") if p.is_file()} - set(before)) == (
        2 + len({w["traffic"] for w in SPEC.bench["workloads"]}) + 1 + 2)
    # every accepted entry is where it was, and the new ones stand behind
    for key in ("configs", "workloads", "end_to_end", "per_layer"):
        was = [e["name"] for e in SPEC.bench[key]]
        assert [e["name"] for e in new.bench[key]][:len(was)] == was
    for m in SPEC.bench["end_to_end"] + SPEC.bench["per_layer"]:
        was = m.get("workloads")
        now = new.metric_entry(m["name"]).get("workloads")
        assert (was is None) == (now is None)
        assert was is None or now[:len(was)] == was
    assert [m["name"] for m in new.bench["per_layer"]][
        len(SPEC.bench["per_layer"]):] == list(added["metrics"])


def test_the_appended_cells_join_the_accepted_lists(appended):
    root, _, added = appended
    new = Spec(str(root))
    assert {"out_tok_per_s", "setup_s"} <= set(new.cell_end_to_end(DECODE))
    assert {"ttft_p90_ms", "gap_p95_ms", "setup_s"} <= set(
        new.cell_end_to_end(RATE))
    for cell, with_all in ((DECODE, added["serve"]), (RATE, added["rate"])):
        shared = set.intersection(*(set(SPEC.cell_layer_metrics(w))
                                    for w in with_all))
        assert len(shared) > 10
        assert shared <= set(new.cell_layer_metrics(cell))
    assert "appended_answer.new" in new.cell_layer_metrics(DECODE)
    assert len(added["beside"]) == len(SPEC.bench["workloads"])
    for cell, accepted in added["beside"].items():
        assert new.cell_end_to_end(cell) == SPEC.cell_end_to_end(accepted)
        assert new.cell_layer_metrics(cell) == [
            n for n in new.cell_layer_metrics(accepted)
            if n != "appended_answer.old"]
    mf = new.layer_metric("appended_answer.old")
    assert new.reader(mf["reader"]["kind"]).read({}, mf["reader"]) == 42


def test_this_directorys_tests_pass_on_the_appended_copy(appended):
    """The copied tests against the copy: ``benchmark`` is the copy's, the
    program the repository's."""
    root, _, _ = appended
    tests = root / "tests" / "benchmark"
    env = {**os.environ, "JAX_PLATFORMS": "cpu",
           "PYTHONPATH": os.pathsep.join([str(root), SPEC.root])}
    where = subprocess.run(
        [sys.executable, "-c",
         "from benchmark.spec import Spec; print(Spec().root)"],
        env=env, cwd=root, capture_output=True, text=True, timeout=120)
    assert where.stdout.strip() == str(root), where.stderr[-2000:]
    p = subprocess.run(
        [sys.executable, "-m", "pytest", str(tests), "-q",
         "--rootdir", str(root), "-p", "no:cacheprovider",
         # a directory of its own: a numbered one beside the running
         # session's would have pytest clear that session's older siblings
         "--basetemp", str(root.parent / "tmp"), "-k", LEFT_OUT_NAMES]
        + [f"--ignore={tests / f}" for f in LEFT_OUT_FILES],
        env=env, cwd=root, capture_output=True, text=True, timeout=600)
    assert p.returncode == 0, p.stdout[-6000:] + p.stderr[-2000:]
