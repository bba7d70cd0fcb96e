"""The readers of the engine loop's phases and of the counters beside them,
each against a ``ctx`` made by hand, and the metrics that use them.

``data/trace_phases.json`` is in the plain form of ``data/trace_small.json``:
one chip, a window of 20,000 ns, three programs, and the host spans the
engine loop writes (``loop.*``, ``engine.*``, flat) with JAX's own inside
two of them. The device runs ops for 3500 + 3000 + 4000 = 10,500 ns; the
9,500 ns it is idle are, by the trace reduction's naming:

    [0, 1000]       before the first decode: loop.idle covers 900, loop.intake
                    100                                     -> loop.idle
    [4500, 5000]    inside the first decode, after its one op  (no host label)
    [5000, 7000]    between the decodes: engine.marshal covers 800,
                    engine.decode 700 (PjitFunction(decode) 650 of it),
                    engine.admit 300, engine.apply and engine.fetch 100
                                                            -> engine.marshal
    [10000, 15000]  the engine had nothing to run: one loop.idle poll covers
                    2000, the next 1800, every other span less
                                                            -> loop.idle
    [19000, 20000]  engine.fetch and the np.asarray inside it both cover all
                    of it; the shorter span names it        -> np.asarray
"""

import http.server
import json
import os
import subprocess
import sys
import threading

import pytest

from benchmark import trace
from benchmark.spec import Spec

HERE = os.path.dirname(os.path.abspath(__file__))
SPEC = Spec()
SAT = ["mistral-7b-int8.decode-sat", "mistral-7b-bf16-tp4.decode-sat"]
PRE = ["mistral-7b-int8.prefill-rate"]
#: every cell the benchmark has had so far, in ``BENCHMARK.json``'s order (the
#: set-up metrics' lists name each): a later cell stands behind them
ALL = [SAT[0], PRE[0], SAT[1], "trinity-mini-bf16.decode-sat-4k",
       "kanana-2-30b-a3b-bf16.decode-sat-8k",
       "kimi-linear-48b-a3b-bf16-ep2.prefill-rate-16k"]
#: metric -> (reader kind, source, the cells that report it)
NEW = {
    "step_gap_mean_ms.sat": ("histogram_mean", "program_span", SAT),
    "pipeline_flush_share.sat": ("counter_ratio", "program_counter", SAT),
    "host_admit_ms.sat": ("step_mean", "program_span", SAT),
    "host_marshal_ms.sat": ("step_mean", "program_span", SAT),
    "host_dispatch_ms.sat": ("step_mean", "program_span", SAT),
    "host_commit_ms.sat": ("step_mean", "program_span", SAT),
    "loop_fetch_share.sat": ("phase_share", "program_span", SAT),
    "loop_idle_share.prefill": ("phase_share", "program_span", PRE),
    "device_stall_share.sat": ("trace_gap_share", "device_trace", SAT),
    "device_stall_share.prefill": ("trace_gap_share", "device_trace", PRE),
    "waiting_peak.prefill": ("step_max", "program_counter", PRE),
    "intake_wait_mean_ms.prefill": ("histogram_mean", "program_span", PRE),
    "weights_s_setup": ("startup_phase", "program_span", ALL),
    "warm_executables_s_setup": ("startup_phase", "program_span", ALL),
    "prefill_programs_per_request.prefill": ("counter_ratio",
                                             "program_counter", PRE),
    "loop_idle_share_traced.prefill": ("phase_share", "program_span", PRE),
}


def read(kind, ctx, **params):
    return SPEC.reader(kind).read(ctx, {"kind": kind, **params})


@pytest.fixture(scope="module")
def red():
    return trace.Reduced(trace.load(os.path.join(HERE, "data",
                                                 "trace_phases.json")))


def test_the_phase_trace_reduces_to_the_gaps_worked_by_hand(red):
    assert red.window_s == pytest.approx(20e-6)
    assert red.busy_s == pytest.approx(10.5e-6)
    assert dict(red.gaps) == {
        "- -> jit_decode | host: loop.idle": pytest.approx(1e-6),
        "inside programs (between ops)": pytest.approx(0.5e-6),
        "jit_decode -> jit_decode | host: engine.marshal":
            pytest.approx(2e-6),
        "jit_decode -> jit_prefill | host: loop.idle": pytest.approx(5e-6),
        "jit_prefill -> - | host: np.asarray(jax.Array)":
            pytest.approx(1e-6)}


def test_stall_share_leaves_out_the_gaps_named_loop_idle(red):
    ctx = {"trace": red}
    # marshal 2000 + np.asarray 1000 + inside programs 500, of 20,000 ns
    stall = read("trace_gap_share", ctx, exclude_host=["loop.idle"])
    assert stall == pytest.approx(17.5)
    # with nothing left out it is the idle share, which the old reader reads
    everything = read("trace_gap_share", ctx)
    assert everything == pytest.approx(47.5)
    assert everything == pytest.approx(read("trace_idle", ctx))
    # and the part left out is the loop.idle gaps: 1000 + 5000. A gap goes
    # whole to the one span that overlaps it most: the polls cover 900 and
    # 3800 of those 6000 ns (23.5%, not 30), so the figure ranks, and the
    # exact share is the idle share less ``phase_share`` between the trace's
    # readings
    assert everything - stall == pytest.approx(30.0)
    only_programs = read("trace_gap_share", ctx, exclude_host=[
        "loop.idle", "engine.marshal", "np.asarray(jax.Array)"])
    assert only_programs == pytest.approx(2.5)


def test_a_run_with_no_trace_reads_no_device_number():
    assert read("trace_gap_share", {"trace": None},
                exclude_host=["loop.idle"]) is None


def test_counter_ratio_is_change_over_change():
    ctx = {"before": {"engine": {"pipeline_flushes": 10, "steps": 100}},
           "after": {"engine": {"pipeline_flushes": 205, "steps": 300}}}
    assert read("counter_ratio", ctx, numerator="pipeline_flushes",
                denominator="steps", scale=100.0) == pytest.approx(97.5)
    assert read("counter_ratio", ctx, numerator="pipeline_flushes",
                denominator="steps") == pytest.approx(0.975)
    # no step in the window, or a program without the counter: nothing
    still = {"before": ctx["after"], "after": ctx["after"]}
    assert read("counter_ratio", still, numerator="pipeline_flushes",
                denominator="steps") is None
    assert read("counter_ratio", ctx, numerator="no_such",
                denominator="steps") is None


def test_counter_ratio_sums_the_entries_of_a_nested_counter():
    ctx = {"before": {"engine": {"requests_finished": 10,
                                 "dispatches_by_phase": {"prefill": 4}}},
           "after": {"engine": {"requests_finished": 30,
                                "dispatches_by_phase": {
                                    "prefill": 16, "chunk": 9, "decode": 500}}}}
    params = SPEC.layer_metric(
        "prefill_programs_per_request.prefill")["reader"]
    # 12 prefill and 9 continuation programs (none before: counted from 0)
    # for 20 requests
    assert SPEC.reader("counter_ratio").read(ctx, params) == \
        pytest.approx(1.05)
    assert read("counter_ratio", ctx, numerator="dispatches_by_phase.decode",
                denominator="requests_finished") == pytest.approx(25.0)
    # the parent commit counts no dispatches
    for marks in ctx.values():
        del marks["engine"]["dispatches_by_phase"]
    assert SPEC.reader("counter_ratio").read(ctx, params) is None


def test_phase_share_is_the_phases_seconds_over_the_seconds_between():
    ctx = {"before": {"t": 100.0, "engine": {"phase_s": {
               "engine.fetch": 50.0, "loop.idle": 20.0}}},
           "after": {"t": 130.0, "engine": {"phase_s": {
               "engine.fetch": 72.5, "loop.idle": 23.0, "engine.chunk": 1.5}}}}
    assert read("phase_share", ctx, phase="engine.fetch") == \
        pytest.approx(75.0)
    assert read("phase_share", ctx, phase="loop.idle") == pytest.approx(10.0)
    # a phase first seen inside the window counts from nothing
    assert read("phase_share", ctx, phase="engine.chunk") == \
        pytest.approx(5.0)
    # the parent commit keeps no phases
    old = {"before": {"t": 100.0, "engine": {}},
           "after": {"t": 130.0, "engine": {}}}
    assert read("phase_share", old, phase="loop.idle") is None


def test_phase_share_between_the_traces_readings():
    """Over the seconds the profiler traced, so that it stands beside the
    device's idle share of the same seconds."""
    ctx = {"before": {"t": 100.0, "engine": {"phase_s": {"loop.idle": 20.0}}},
           "after": {"t": 130.0, "engine": {"phase_s": {"loop.idle": 23.0}}},
           "trace_before": {"t": 110.0,
                            "engine": {"phase_s": {"loop.idle": 21.0}}},
           "trace_after": {"t": 114.0,
                           "engine": {"phase_s": {"loop.idle": 21.5}}}}
    params = SPEC.layer_metric("loop_idle_share_traced.prefill")["reader"]
    assert SPEC.reader("phase_share").read(ctx, params) == \
        pytest.approx(12.5)
    assert read("phase_share", ctx, phase="loop.idle") == pytest.approx(10.0)
    # an untraced run took no such readings
    ctx["trace_before"] = ctx["trace_after"] = None
    assert SPEC.reader("phase_share").read(ctx, params) is None


def test_step_max_is_over_the_steps_inside_the_window():
    steps = [{"ts": 9.0, "waiting_peak": 7}, {"ts": 10.0, "waiting_peak": 2},
             {"ts": 11.0, "waiting_peak": 4}, {"ts": 12.0, "waiting_peak": 1},
             {"ts": 40.0, "waiting_peak": 9}]
    ctx = {"wall0": 10.0, "wall1": 40.0, "steps": steps}
    assert read("step_max", ctx, field="waiting_peak") == 4
    # records of the parent commit carry no such field
    ctx["steps"] = [{"ts": 11.0, "waiting": 3}]
    assert read("step_max", ctx, field="waiting_peak") is None


def test_the_existing_readers_read_the_new_record_fields_and_histogram():
    steps = [{"ts": 1.0, "kind": "decode", "running": 8, "admit_ms": 0.2,
              "dispatch_ms": 2.0},
             {"ts": 2.0, "kind": "decode", "running": 8, "admit_ms": 0.4,
              "dispatch_ms": 3.0},
             {"ts": 3.0, "kind": "idle", "running": 0, "admit_ms": 9.0,
              "dispatch_ms": 9.0}]
    ctx = {"wall0": 0.0, "wall1": 10.0, "steps": steps}
    for metric, want in (("host_admit_ms.sat", 0.3),
                         ("host_dispatch_ms.sat", 2.5)):
        params = SPEC.layer_metric(metric)["reader"]
        assert SPEC.reader(params["kind"]).read(ctx, params) == \
            pytest.approx(want)
    hist = {"before": {"histograms": {"intake_wait_seconds": {
                "sum": 1.0, "count": 10}}},
            "after": {"histograms": {"intake_wait_seconds": {
                "sum": 1.6, "count": 50}}}}
    params = SPEC.layer_metric("intake_wait_mean_ms.prefill")["reader"]
    assert SPEC.reader(params["kind"]).read(hist, params) == \
        pytest.approx(15.0)


@pytest.fixture()
def stats_server():
    """A server that answers ``GET /stats`` with what the test put in
    ``payload``: stands in for the system under test's own."""
    payload = {}

    class Handler(http.server.BaseHTTPRequestHandler):
        def do_GET(self):
            body = json.dumps(payload).encode()
            self.send_response(200 if self.path == "/stats" else 404)
            self.send_header("content-length", str(len(body)))
            self.end_headers()
            self.wfile.write(body)

        def log_message(self, *args):
            pass

    srv = http.server.HTTPServer(("127.0.0.1", 0), Handler)
    threading.Thread(target=srv.serve_forever, daemon=True).start()

    class Sut:
        base = f"http://127.0.0.1:{srv.server_address[1]}"

    yield payload, {"sut": Sut()}
    srv.shutdown()
    srv.server_close()


def test_startup_phase_reads_the_servers_own_record(stats_server):
    payload, ctx = stats_server
    payload["startup"] = {"weights_s": 12.5, "warm_executables_s": 35.25,
                          "total_s": 53.0}
    assert read("startup_phase", ctx, phase="warm_executables_s") == 35.25
    assert read("startup_phase", ctx, phase="weights_s") == 12.5
    assert read("startup_phase", ctx, phase="no_such_s") is None
    # the parent commit's /stats has no such section
    del payload["startup"]
    assert read("startup_phase", ctx, phase="warm_executables_s") is None


def test_the_new_entries_keep_the_rules():
    assert SPEC.problems() == []
    for name, (kind, source, cells) in NEW.items():
        entry, mf = SPEC.metric_entry(name), SPEC.layer_metric(name)
        assert mf["reader"]["kind"] == kind and entry["source"] == source
        assert [w for w in ALL
                if name in SPEC.cell_layer_metrics(w)] == cells
        # every cell is listed by name, the set-up metrics' too: the
        # benchmark's own no-edit test pins which entries have no list.
        # The list STARTS with these cells: a later cell may join behind
        assert entry["workloads"][:len(cells)] == cells
    by_layer = {SPEC.metric_entry(n)["layer"] for n in NEW}
    assert by_layer == {"admission and scheduler", "device", "executables"}
    # what a share of the loop thread's time should do is said: the loop
    # waiting for the device, or for work, is the good case
    assert SPEC.metric_entry("loop_fetch_share.sat")["better"] == "higher"
    assert SPEC.metric_entry("loop_idle_share.prefill")["better"] == "higher"
    assert SPEC.metric_entry("device_stall_share.sat")["better"] == "lower"


def test_a_traced_dry_run_prints_the_new_program_metrics(tmp_path):
    """On the CPU, tiny: every new metric that is read from the program
    appears in the cell's line, and none that is read from the device."""
    cell = "mistral-7b-bf16-tp4.decode-sat"
    p = subprocess.run(
        [sys.executable, os.path.join(SPEC.root, "benchmark", "run.py"),
         "--workload", cell, "--seed", "3000000019", "--seconds", "2",
         "--trace", "1", "--dry-run", "--out", str(tmp_path / "out")],
        env={**os.environ, "JAX_PLATFORMS": "cpu"}, cwd=SPEC.root,
        capture_output=True, text=True, timeout=600)
    assert p.returncode == 0, p.stderr[-3000:]
    metrics = json.loads(p.stdout.strip().splitlines()[-1])["metrics"]
    want = {n for n, (_, source, cells) in NEW.items()
            if cell in cells and source != "device_trace"}
    assert want <= set(metrics), want - set(metrics)
    assert not any(SPEC.metric_entry(n)["source"] == "device_trace"
                   for n in metrics)
    assert "left out" not in p.stderr
    assert 0 <= metrics["pipeline_flush_share.sat"]["value"] <= 100
    assert 0 < metrics["loop_fetch_share.sat"]["value"] < 100
    assert metrics["warm_executables_s_setup"]["value"] > 0
