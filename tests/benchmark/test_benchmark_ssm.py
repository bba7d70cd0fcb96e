"""What PR 45 adds to the benchmark: the Nemotron-3-Nano configuration keeps
the rules (and everything that was there is still there, in its order,
before it), its published keys are pinned, the traffic is the issue's, the
stage's bytes and the recurrent blocks' work against hand-worked counts, the
``.ssm`` metrics are the new cell's alone among the cells that were there,
the cell stands behind the accepted cells of every list it joins and in none
that prices another architecture, and the new cell's dry run on the CPU.
Written to PR 44's rule: lists are held by prefixes and known places, never
by their end, their length or their whole."""

import json
import os
import subprocess
import sys

import pytest

from benchmark import trace
from benchmark.spec import Spec
from test_benchmark_spec import assert_published_keys_unchanged

SPEC = Spec()
NAME = "nemotron-3-nano-30b-a3b-bf16-ep2"
CELL = NAME + ".decode-sat-1k"
M = SPEC.config(NAME)
S = SPEC.shapes("shapes_ssm")
SSM = ["ssm_decode_share.ssm", "ssm_prefill_share.ssm",
       "ssm_decode_hbm_roofline.ssm", "ssm_prefill_mxu_roofline.ssm",
       "moe_streamed_hbm_roofline.ssm", "decode_hbm_roofline.ssm",
       "state_slots_peak.ssm"]
#: what the accepted benchmark held before this PR, in its order
CONFIGS_BEFORE = ["mistral-7b-int8", "mistral-7b-bf16-tp4",
                  "trinity-mini-bf16", "kanana-2-30b-a3b-bf16",
                  "kimi-linear-48b-a3b-bf16-ep2"]
CELLS_BEFORE = ["mistral-7b-int8.decode-sat", "mistral-7b-int8.prefill-rate",
                "mistral-7b-bf16-tp4.decode-sat",
                "trinity-mini-bf16.decode-sat-4k",
                "kanana-2-30b-a3b-bf16.decode-sat-8k",
                "kimi-linear-48b-a3b-bf16-ep2.prefill-rate-16k"]
SATURATED_BEFORE = [CELLS_BEFORE[i] for i in (0, 2, 3, 4)]
ROUTED_BEFORE = CELLS_BEFORE[3:5]
N_LAYER_METRICS_BEFORE = 92
#: the lists the cell joins, behind the accepted cells each held
JOINS_SATURATED = [
    "decode_batch_mean.sat", "kv_util_peak.sat", "preemptions.sat",
    "decode_step_ms.sat", "pallas_busy_share.sat", "device_idle_share.sat",
    "gap_p50_ms.sat", "pad_fraction_decode.sat", "decode_uploads_per_step",
    "engine_tok_per_s.serve", "stream_wake_mean_ms.serve",
    "stream_encode_mean_ms.serve", "stream_write_mean_ms.serve",
    "stream_deliver_mean_ms.serve", "stream_finish_lag_mean_ms.serve",
    "stream_deliver_p99_ms.serve", "callers_draining_mean.serve",
    "callers_ingress_mean.serve", "stream_backlog_peak.serve",
    "ingress_mean_ms.serve", "loop_offcpu_share.serve",
    "stream_tokens_per_event.serve"]
JOINS_ROUTED = [
    "experts_touched_mean.moe", "expert_load_max_over_mean.moe",
    "moe_ffn_share.moe", "step_gap_mean_ms.moe", "pipeline_flush_share.moe",
    "host_admit_ms.moe", "host_marshal_ms.moe", "host_dispatch_ms.moe",
    "host_commit_ms.moe", "loop_fetch_share.moe", "device_stall_share.moe",
    "moe_streamed_share.moe"]
#: lists that price another architecture's bytes, a window or a latent cache
STAYS_OUT = ["moe_streamed_hbm_roofline.moe", "decode_hbm_roofline.sat",
             "decode_hbm_roofline.moe", "decode_hbm_roofline.mla",
             "window_skipped_share.moe", "pool_dead_share.moe",
             "mla_decode_share.mla", "mla_decode_hbm_roofline.mla",
             "mla_decode_mxu_roofline.mla", "latent_visible_mean.mla",
             "state_slots_peak.kda"]


# -- the configuration and the cell keep the rules ---------------------------

def test_the_benchmark_is_whole_and_what_was_there_comes_first():
    """Appended, not inserted: every accepted configuration, cell and
    per-layer metric is where it was, and this PR's come behind them (not
    necessarily LAST: the next PR appends too)."""
    assert SPEC.problems() == []
    b = SPEC.bench
    assert [c["name"] for c in b["configs"]][:5] == CONFIGS_BEFORE
    assert [w["name"] for w in b["workloads"]][:6] == CELLS_BEFORE
    names = [m["name"] for m in b["per_layer"]]
    assert names[N_LAYER_METRICS_BEFORE - 1] == "stream_tokens_per_event.serve"
    assert names[N_LAYER_METRICS_BEFORE:N_LAYER_METRICS_BEFORE + 7] == SSM
    assert b["configs"][5]["name"] == NAME
    assert b["workloads"][6]["name"] == CELL
    assert b["workloads"][6]["chips"] == 1 == M["chips"]
    assert b["configs"][5]["reduced"] == M["reduced"]
    assert b["configs"][5]["source"] == M["source"]
    assert M["model_type"] == "nemotron_h"
    assert SPEC.cell_end_to_end(CELL) == ["out_tok_per_s", "setup_s"]
    assert SPEC.metric_entry("out_tok_per_s")["workloads"][:5] == (
        SATURATED_BEFORE + [CELL])
    assert len(b["workloads"][6]["why"]) <= 200
    assert len(b["configs"][5]["why"]) <= 200


def test_the_published_keys_are_pinned():
    assert_published_keys_unchanged(SPEC, NAME)
    with open(os.path.join(SPEC.root, "tests", "benchmark", "data",
                           "published", NAME + ".json")) as f:
        pinned = json.load(f)
    widths = {"hidden_size": 2688, "intermediate_size": 1856,
              "moe_intermediate_size": 1856,
              "moe_shared_expert_intermediate_size": 3712,
              "mamba_num_heads": 64, "mamba_head_dim": 64,
              "ssm_state_size": 128, "n_groups": 8, "conv_kernel": 4,
              "head_dim": 128, "num_attention_heads": 32,
              "num_key_value_heads": 2, "num_experts_per_tok": 6,
              "n_shared_experts": 1, "vocab_size": 131072, "expand": 2,
              "chunk_size": 128, "routed_scaling_factor": 2.5,
              "max_position_embeddings": 262144}
    for key, value in widths.items():
        assert pinned[key] == M[key] == value, key
    assert pinned["mlp_hidden_act"] == "relu2" and pinned[
        "use_conv_bias"] is True
    # what was cut stands beside what was published
    pub = M["published"]
    assert pub == {"num_hidden_layers": 52, "n_routed_experts": 128,
                   "hybrid_override_pattern": (
                       "MEMEM*EMEMEM*EMEMEM*EMEMEM*EMEMEM*EMEMEMEM*EMEMEMEME")}
    assert pub["hybrid_override_pattern"].startswith(
        M["hybrid_override_pattern"])
    assert (M["num_hidden_layers"], M["hybrid_override_pattern"],
            M["n_routed_experts"]) == (9, "MEMEM*EME", 64)
    assert M["reduced"] == ["num_hidden_layers", "hybrid_override_pattern",
                            "n_routed_experts"]
    for said in ("equations", "inner width", "no positional embedding",
                 "time_step_limit", "chunk_size", "state precision",
                 "experts' width", "weights", "experts held",
                 "tokens an expert sees", "max_num_seqs",
                 "max_prefill_batch"):
        assert said in M["assumed"], said
    assert "TWO chips" in M["deployment"]


@pytest.mark.parametrize("key,value", [
    ("loop", "closed"), ("clients", 132),
    ("prompt_tokens", {"dist": "loguniform", "lo": 128, "hi": 1024}),
    ("output_tokens", {"dist": "uniform", "lo": 256, "hi": 1024})])
def test_the_traffic_is_the_issues(key, value):
    mix = SPEC.traffic("decode-sat-1k")
    assert mix[key] == value
    assert 30 <= mix["warmup_s"] <= 60
    eng = M["engine"]
    # at most four wait: half the gate's line of eight
    assert mix["clients"] - eng["max_num_seqs"] == 4
    assert mix["prompt_tokens"]["hi"] + mix["output_tokens"]["hi"] <= eng[
        "max_model_len"]
    assert mix["output_tokens"]["hi"] == eng["max_new_tokens"]
    # prompts on both sides of the largest bucket: some carry state and tail
    assert mix["prompt_tokens"]["lo"] < max(
        eng["context_encoding_buckets"]) < mix["prompt_tokens"]["hi"]
    assert "sizes_seed" in mix["assumed"] and "warmup_s" in mix["assumed"]
    ref = M["reference"]["prompt_tokens"]
    assert min(ref) < 256 and max(ref) > 1536      # every rung's carry


def test_the_memory_table_is_the_issues_arithmetic():
    mem = M["memory"]
    t = mem["table"]
    first = lambda p: [v for k, v in t.items() if k.startswith(p)][0]  # noqa: E731
    stage = first("this stage")
    assert stage == mem["weights_bytes"] == pytest.approx(7.04e9, rel=2e-3)
    m_block, a_block = first("one Mamba-2"), first("one attention block")
    assert (m_block, a_block) == (2 * 38_744_896, 2 * 23_399_040)
    assert first("one routed expert") == 2 * 9_977_856
    held, whole = first("one routed block, 64"), first(
        "one routed block, all")
    assert held == pytest.approx(1.318e9, rel=1e-3)
    assert whole == pytest.approx(2.595e9, rel=1e-3)
    assert 4 * m_block + a_block + 4 * held + first("embedding") == stage
    assert first("the same nine blocks") == pytest.approx(12.15e9, rel=2e-3)
    assert first("the whole model") == pytest.approx(63.2e9, rel=2e-3)
    assert first("one slot") == 4 * (2_097_152 + 36_864)
    assert mem["state_arena_bytes"] == 129 * first("one slot")
    assert mem["kv_pool_bytes"] == M["engine"]["num_blocks"] * 16 * first(
        "one token in the paged pool")
    assert mem["sum_bytes"] == (mem["weights_bytes"] + mem["kv_pool_bytes"]
                                + mem["state_arena_bytes"])
    assert mem["sum_bytes"] > 0.25 * 16 * 2 ** 30     # the driver's floor


# -- the arithmetic ----------------------------------------------------------

def test_a_steps_fixed_bytes_by_hand():
    mixer = 2688 * (4096 + 6144 + 64) + 4096 * 2688
    assert S.mixer_params(M) == mixer == 38_707_200
    attn = 2 * 2688 * 128 * (32 + 2)
    assert S.attention_params(M) == attn == 23_396_352
    assert S.expert_bytes(M, 2) == 2 * 2688 * 1856 * 2 == 19_955_712
    fixed = ((4 * mixer + attn + 4 * 2 * 2688 * 3712 + 2688 * 131072) * 2
             + 4 * 2688 * 128 * 4)
    assert S.fixed_bytes_per_step(M, 2) == fixed
    assert fixed == pytest.approx(1.22e9, rel=0.01)


def test_the_recurrence_the_state_and_the_held_experts_by_hand():
    # 4 P N operations a token and head: 64 heads of 64 x 128
    assert S.recurrence_flops(M, 1) == 4 * 64 * 64 * 128 == 2_097_152
    f = S.FUNCTIONS["ssm_recurrence_flops"]
    assert f["peak"] == "bf16_flops_per_s"
    assert f["work"](M, programs=9, counters={"layer_tokens": 8192}) == (
        2_097_152.0 * 8192)
    # a row's state read and written: 2 x 64 x 64 x 128 x 4 B
    g = S.FUNCTIONS["ssm_state_bytes"]
    assert g["peak"] == "hbm_bytes_per_s"
    assert g["work"](M, programs=2, counters={"layer_rows": 24}) == (
        24 * 4_194_304.0)
    # of 125 touched a routed-block step at most 64 are held elsewhere
    assert S.held_experts_touched(M, 500, 4) == 500 - 64 * 4
    assert S.held_experts_touched(M, 200, 4) == 0
    h = S.FUNCTIONS["streamed_expert_bytes"]
    assert h["work"](M, programs=0, counters={
        "experts_touched": 500, "layer_steps": 4}) == 244 * 19_955_712.0
    d = S.FUNCTIONS["decode_bytes"]
    assert d["peak"] == "hbm_bytes_per_s"
    assert d["work"](M, programs=1, counters={
        "experts_touched": 500, "layer_steps": 4, "layer_rows": 512}) == (
        S.fixed_bytes_per_step(M, 2) + 244 * 19_955_712.0
        + 512 * 4_194_304.0)
    # the issue's 8.8 GB a step at 128 rows with every held expert touched
    assert d["work"](M, programs=1, counters={
        "experts_touched": 512, "layer_steps": 4,
        "layer_rows": 512}) == pytest.approx(8.5e9, rel=0.02)


def test_the_counted_reader_prices_the_kernels_from_the_counters():
    """``trace_roofline_counted`` over a made-up trace: 512 layer-rows
    through a step kernel that took 4 ms is 512 x 4 MiB / 819e9 / 0.004 s;
    a parent without the counter reads nothing and does not raise."""
    read = SPEC.reader("trace_roofline_counted").read
    mf = SPEC.layer_metric("ssm_decode_hbm_roofline.ssm")

    class Red:
        def op_total_s(self, patterns):
            return 0.004

        def program_total_s(self, pattern):
            return 0.004

    ctx = {"trace": Red(), "spec": SPEC, "config": M,
           "peak": SPEC.peak("TPU v5 lite"),
           "trace_before": {"engine": {"ssm": {"rows_stepped": 1000}}},
           "trace_after": {"engine": {"ssm": {"rows_stepped": 1512}}}}
    want = 100.0 * (4_194_304.0 * 512 / 819e9) / 0.004
    assert read(ctx, mf["reader"]) == pytest.approx(want)
    assert 0 < want < 100
    ctx["trace_after"] = {"engine": {}}
    for name in SSM[:6]:
        reader = SPEC.layer_metric(name)["reader"]
        if reader["kind"] == "trace_roofline_counted":
            assert read(ctx, reader) is None, name


# -- the metrics -------------------------------------------------------------

@pytest.mark.parametrize("name", SSM)
def test_the_new_metrics_are_the_new_cells_alone(name):
    """Alone among the cells that were there; the first of its list, behind
    which a second configuration of the family may join."""
    entry, mf = SPEC.metric_entry(name), SPEC.layer_metric(name)
    assert entry["workloads"][0] == CELL
    assert entry["moves"] == mf["moves"] == "out_tok_per_s"
    assert name in SPEC.cell_layer_metrics(CELL)
    for w in CELLS_BEFORE:
        assert name not in SPEC.cell_layer_metrics(w)
    if "roofline" in name:
        assert entry["unit"] == "%" and entry["better"] == "higher"
        assert entry["source"] == "device_trace"


@pytest.mark.parametrize("name", JOINS_SATURATED + JOINS_ROUTED + [
    "weights_s_setup", "warm_executables_s_setup"])
def test_the_cell_stands_behind_the_accepted_cells_of_a_list(name):
    cells = SPEC.metric_entry(name)["workloads"]
    before = (SATURATED_BEFORE if name in JOINS_SATURATED else
              ROUTED_BEFORE if name in JOINS_ROUTED else CELLS_BEFORE)
    assert cells[:len(before) + 1] == before + [CELL]
    assert name in SPEC.cell_layer_metrics(CELL)


@pytest.mark.parametrize("name", ["xla_compile_s_setup",
                                  "cache_entries_added"])
def test_the_cell_reports_what_every_cell_reports(name):
    assert "workloads" not in SPEC.metric_entry(name)
    assert name in SPEC.cell_layer_metrics(CELL)


@pytest.mark.parametrize("name", STAYS_OUT)
def test_the_cell_joins_no_list_that_prices_another_architecture(name):
    """``moe_streamed_hbm_roofline.moe`` prices THREE matrices an expert
    and would read 1.5 times too high here; the ``decode_hbm_roofline.*``
    of other models price their own fixed bytes; a window, a latent cache
    this model has not; ``state_slots_peak.kda`` moves ``ttft_p90_ms``,
    which this cell does not report (its twin ``.ssm`` reads the same
    field)."""
    assert CELL not in SPEC.metric_entry(name)["workloads"]
    assert name not in SPEC.cell_layer_metrics(CELL)


def test_the_slot_peaks_twin_reads_what_the_accepted_one_reads():
    a, b = (SPEC.layer_metric(n) for n in ("state_slots_peak.ssm",
                                           "state_slots_peak.kda"))
    assert a["reader"] == b["reader"]
    assert (a["layer"], a["unit"], a["better"], a["source"]) == (
        b["layer"], b["unit"], b["better"], b["source"])
    assert (a["moves"], b["moves"]) == ("out_tok_per_s", "ttft_p90_ms")


def test_the_ops_the_shares_name_are_the_programs_own():
    from scalable_hw_agnostic_inference_tpu.ops.pallas import (
        moe_ffn,
        ssm_chunk,
        ssm_step,
    )

    pat = lambda n: SPEC.layer_metric(n)["reader"]        # noqa: E731
    assert pat("ssm_prefill_share.ssm")["patterns"] == [
        ssm_chunk.KERNEL_NAME] == pat("ssm_prefill_mxu_roofline.ssm")["ops"]
    assert pat("ssm_decode_share.ssm")["patterns"] == [
        ssm_step.KERNEL_NAME] == pat("ssm_decode_hbm_roofline.ssm")["ops"]
    assert pat("moe_streamed_hbm_roofline.ssm")["ops"] == [
        moe_ffn.KERNEL_NAME] == SPEC.layer_metric(
        "moe_streamed_hbm_roofline.moe")["reader"]["ops"]
    assert pat("decode_hbm_roofline.ssm")["pattern"] == "^jit_decode"
    counters = {c for n in SSM[:6] for c in pat(n).get(
        "counters", {}).values()}
    assert counters == {"ssm.rows_stepped", "ssm.prefill_tokens",
                        "moe.experts_touched", "moe.layer_steps"}


def test_a_made_up_trace_gives_the_kernels_shares():
    red = trace.Reduced.__new__(trace.Reduced)
    red.op_s = {"ssm_chunk_prefill": 0.001, "ssm_decode_step": 0.003,
                "fusion.7": 0.004}
    red.window_s = 0.010
    read = SPEC.reader("trace_op_share").read
    ctx = {"trace": red}
    assert read(ctx, SPEC.layer_metric("ssm_prefill_share.ssm")[
        "reader"]) == pytest.approx(10.0)
    assert read(ctx, SPEC.layer_metric("ssm_decode_share.ssm")[
        "reader"]) == pytest.approx(30.0)


def test_the_tolerance_says_what_it_refuses_and_what_it_cannot():
    ref = SPEC.reference(M["reference"]["module"])
    tol = SPEC.tolerance(M["reference"]["tolerance"])
    assert set(tol) >= {"max_abs_logprob_diff", "mean_abs_logprob_diff",
                        "top1_must_match_above_margin", "reason"}
    for name in (ref.REFUSED_VARIANTS + ref.REFUSED_BY_MEAN
                 + ref.NOT_REFUSED_RELIABLY):
        assert name in tol["reason"], name
    assert "weights_fp8" in ref.REFUSED_BY_MEAN


# -- the cell's dry run -------------------------------------------------------

def test_the_new_cells_dry_run_ends_correct(tmp_path):
    """Traced: the tiny state-space stand-in behind the real server, the
    reference check through prefill and recurrent decode, the closed loop,
    the contract's last line with every metric of the cell that the program
    (not the device) gives."""
    p = subprocess.run(
        [sys.executable, os.path.join(SPEC.root, "benchmark", "run.py"),
         "--workload", CELL, "--seed", "3000000019", "--seconds", "2",
         "--trace", "1", "--dry-run", "--out", str(tmp_path / "out")],
        env={**os.environ, "JAX_PLATFORMS": "cpu"}, cwd=SPEC.root,
        capture_output=True, text=True, timeout=600)
    assert p.returncode == 0, p.stderr[-3000:]
    lines = p.stdout.strip().splitlines()
    result = json.loads(lines[-1])
    assert result["correct"] is True and result["failed"] == 0
    assert result["attempted"] > 0
    want = {n for n in SPEC.cell_layer_metrics(CELL)
            if SPEC.metric_entry(n)["source"] != "device_trace"}
    assert want <= set(result["metrics"]), want - set(result["metrics"])
    assert result["metrics"]["state_slots_peak.ssm"]["value"] >= 1
    assert "left out" not in p.stderr
    ref = json.loads([ln for ln in lines
                      if ln.startswith("reference ")][0][10:])
    assert ref["passed"] and ref["positions"] == 8
