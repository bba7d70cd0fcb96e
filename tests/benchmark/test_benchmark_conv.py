"""What PR 50 adds to the benchmark: the LFM2-24B-A2B configuration keeps
the rules (and everything that was there is still there, in its order,
before it), its published keys are pinned, the traffic is the issue's, the
stage's bytes against hand-worked counts, the ``.conv`` metrics are the new
cell's alone among the cells that were there, the cell stands behind the
accepted cells of every list it joins and in none that prices another
architecture, and the new cell's dry run on the CPU. Written to PR 44's
rule: lists are held by prefixes and known places, never by their end, their
length or their whole."""

import json
import os
import subprocess
import sys

import pytest

from benchmark import trace
from benchmark.spec import Spec
from test_benchmark_spec import assert_published_keys_unchanged

SPEC = Spec()
NAME = "lfm2-24b-a2b-bf16"
CELL = NAME + ".decode-sat-turns"
M = SPEC.config(NAME)
S = SPEC.shapes("shapes_conv")
CONV = ["state_slots_peak.conv", "paged_attn_share.conv",
        "moe_streamed_hbm_roofline.conv", "decode_hbm_roofline.conv"]
#: what the accepted benchmark held before this PR, in its order
CONFIGS_BEFORE = ["mistral-7b-int8", "mistral-7b-bf16-tp4",
                  "trinity-mini-bf16", "kanana-2-30b-a3b-bf16",
                  "kimi-linear-48b-a3b-bf16-ep2",
                  "nemotron-3-nano-30b-a3b-bf16-ep2"]
CELLS_BEFORE = ["mistral-7b-int8.decode-sat", "mistral-7b-int8.prefill-rate",
                "mistral-7b-bf16-tp4.decode-sat",
                "trinity-mini-bf16.decode-sat-4k",
                "kanana-2-30b-a3b-bf16.decode-sat-8k",
                "kimi-linear-48b-a3b-bf16-ep2.prefill-rate-16k",
                "nemotron-3-nano-30b-a3b-bf16-ep2.decode-sat-1k"]
SATURATED_BEFORE = [CELLS_BEFORE[i] for i in (0, 2, 3, 4, 6)]
ROUTED_BEFORE = [CELLS_BEFORE[i] for i in (3, 4, 6)]
N_LAYER_METRICS_BEFORE = 99
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
#: lists that price another architecture's bytes, state, window or cache
STAYS_OUT = ["moe_streamed_hbm_roofline.moe", "moe_streamed_hbm_roofline.ssm",
             "decode_hbm_roofline.sat", "decode_hbm_roofline.moe",
             "decode_hbm_roofline.mla", "decode_hbm_roofline.ssm",
             "ssm_decode_share.ssm", "ssm_prefill_share.ssm",
             "ssm_decode_hbm_roofline.ssm", "ssm_prefill_mxu_roofline.ssm",
             "state_slots_peak.ssm", "state_slots_peak.kda",
             "pool_dead_share.moe", "mla_decode_share.mla",
             "latent_visible_mean.mla"]


# -- the configuration and the cell keep the rules ---------------------------

def test_the_benchmark_is_whole_and_what_was_there_comes_first():
    """Appended, not inserted: every accepted configuration, cell and
    per-layer metric is where it was, and this PR's come behind them (not
    necessarily LAST: the next PR appends too)."""
    assert SPEC.problems() == []
    b = SPEC.bench
    assert [c["name"] for c in b["configs"]][:6] == CONFIGS_BEFORE
    assert [w["name"] for w in b["workloads"]][:7] == CELLS_BEFORE
    names = [m["name"] for m in b["per_layer"]]
    assert names[N_LAYER_METRICS_BEFORE - 1] == "state_slots_peak.ssm"
    assert names[N_LAYER_METRICS_BEFORE:N_LAYER_METRICS_BEFORE + 4] == CONV
    assert b["configs"][6]["name"] == NAME
    assert b["workloads"][7]["name"] == CELL
    assert b["workloads"][7]["chips"] == 1 == M["chips"]
    assert b["configs"][6]["reduced"] == M["reduced"]
    assert b["configs"][6]["source"] == M["source"]
    assert M["model_type"] == "lfm2_moe"
    assert SPEC.cell_end_to_end(CELL) == ["out_tok_per_s", "setup_s"]
    assert SPEC.metric_entry("out_tok_per_s")["workloads"][:6] == (
        SATURATED_BEFORE + [CELL])
    why = b["workloads"][7]["why"]
    assert len(why) <= 200 and len(b["configs"][6]["why"]) <= 200
    assert "2 layers of 9" in why and "host" in why


def test_the_published_keys_are_pinned():
    assert_published_keys_unchanged(SPEC, NAME)
    with open(os.path.join(SPEC.root, "tests", "benchmark", "data",
                           "published", NAME + ".json")) as f:
        pinned = json.load(f)
    widths = {"hidden_size": 2048, "intermediate_size": 11776,
              "moe_intermediate_size": 1536, "num_attention_heads": 32,
              "num_key_value_heads": 8, "num_experts": 64,
              "num_experts_per_tok": 4, "conv_L_cache": 3,
              "vocab_size": 65536, "routed_scaling_factor": 1,
              "norm_eps": 1e-05, "max_position_embeddings": 128000}
    for key, value in widths.items():
        assert pinned[key] == M[key] == value, key
    assert pinned["rope_parameters"] == M["rope_parameters"] == {
        "rope_theta": 1000000, "rope_type": "default"}
    assert pinned["conv_bias"] is False and pinned["use_expert_bias"] is True
    assert pinned["norm_topk_prob"] is True
    # what was cut stands beside what was published
    pub = M["published"]
    assert (pub["num_hidden_layers"], pub["num_dense_layers"]) == (40, 2)
    assert len(pub["layer_types"]) == 40
    assert pub["layer_types"][1:10] == M["layer_types"]
    assert (pub["layer_types"].count("conv"),
            pub["layer_types"].count("full_attention")) == (30, 10)
    assert (M["num_hidden_layers"], M["num_dense_layers"]) == (9, 1)
    assert M["reduced"] == ["num_hidden_layers", "num_dense_layers",
                            "layer_types"]
    for said in ("equations", "tie_word_embeddings", "head_dim",
                 "the norm's form", "rotary after the head norms",
                 "dense width", "expert bias", "route epsilon", "head lanes",
                 "state precision", "weights", "experts held",
                 "max_num_seqs", "max_model_len", "max_prefill_batch"):
        assert said in M["assumed"], said
    assert "every expert of a layer" in M["deployment"]
    assert "larger part of a step than forty" in M["deployment"]


@pytest.mark.parametrize("key,value", [
    ("loop", "closed"), ("clients", 132), ("sizes_seed", 5001),
    ("prompt_tokens", {"dist": "loguniform", "lo": 128, "hi": 1024})])
def test_the_traffic_is_the_issues(key, value):
    mix = SPEC.traffic("decode-sat-turns")
    assert mix[key] == value
    out = mix["output_tokens"]
    # ISSUE 50: uniform 128-512, or the ONE change it allows, 64-256
    assert out in ({"dist": "uniform", "lo": 128, "hi": 512},
                   {"dist": "uniform", "lo": 64, "hi": 256})
    assert 10 <= mix["warmup_s"] <= 30
    eng = M["engine"]
    # at most four wait: half the gate's line of eight
    assert mix["clients"] - eng["max_num_seqs"] == 4
    assert mix["prompt_tokens"]["hi"] + 512 <= eng["max_model_len"]
    assert eng["max_new_tokens"] == 512 >= out["hi"]
    # prompts on both sides of the largest bucket: some carry a tail
    assert mix["prompt_tokens"]["lo"] < max(
        eng["context_encoding_buckets"]) < mix["prompt_tokens"]["hi"]
    assert "sizes_seed" in mix["assumed"] and "warmup_s" in mix["assumed"]
    ref = M["reference"]["prompt_tokens"]
    # each bucket, and twice the continuation chunk that reads the tail
    assert min(ref) < 256 < sorted(ref)[1] < 512 < sorted(ref)[2] < max(
        ref) <= mix["prompt_tokens"]["hi"]


def test_the_memory_table_is_the_issues_arithmetic():
    mem = M["memory"]
    t = mem["table"]
    first = lambda p: [v for k, v in t.items() if k.startswith(p)][0]  # noqa: E731
    expert = first("one routed expert")
    assert expert == 3 * 2048 * 1536 * 2 == 18_874_368
    assert first("one routed layer's") == 64 * expert == 1_207_959_552
    assert first("8 routed layers'") == 9_663_676_416
    conv, attn = first("one conv mixer"), first("one attention mixer")
    assert (conv, attn) == (2 * 16_783_360, 2 * 10_485_888)
    dense, emb = first("the dense MLP"), first("embedding = head")
    assert (dense, emb) == (144_703_488, 268_435_456)
    rest = first("7 conv and 2 attention")
    assert rest == 7 * conv + 2 * attn + dense + 8 * first(
        "one router") + 19 * 2048 * 2
    assert rest == pytest.approx(0.426e9, rel=0.01)
    stage = first("this stage")
    assert stage == mem["weights_bytes"] == 8 * 64 * expert + rest + emb
    assert stage == pytest.approx(10.36e9, rel=1e-3)
    assert first("the same stage with the head untied") == stage + emb
    assert first("a third period") > 15.0e9          # why two periods
    assert first("the whole model") == pytest.approx(47.7e9, rel=2e-3)
    assert first("one slot") == 7 * 8192
    assert mem["state_arena_bytes"] == 129 * first("one slot")
    declared = first("one token in the paged pool, DECLARED")
    held = first("one token in the paged pool, HELD")
    assert (declared, held) == (4096, 8192)
    assert mem["kv_pool_bytes"] == M["engine"]["num_blocks"] * 16 * held
    assert mem["sum_bytes"] == (mem["weights_bytes"] + mem["kv_pool_bytes"]
                                + mem["state_arena_bytes"])
    assert mem["sum_bytes"] == pytest.approx(12.05e9, rel=1e-3)
    assert mem["sum_bytes"] > 0.25 * 16 * 2 ** 30     # the driver's floor


# -- the arithmetic ----------------------------------------------------------

def test_a_steps_fixed_bytes_by_hand():
    assert S.conv_mixer_params(M) == 2048 * 6144 + 2048 * 2048 == 16_777_216
    assert S.attention_params(M) == 2 * 2048 * 64 * (32 + 8) == 10_485_760
    assert S.expert_bytes(M, 2) == 18_874_368
    fixed = ((7 * 16_777_216 + 2 * 10_485_760 + 3 * 2048 * 11776
              + 2048 * 65536) * 2 + 8 * 2048 * 64 * 4)
    assert S.fixed_bytes_per_step(M, 2) == fixed
    assert fixed == pytest.approx(0.694e9, rel=0.01)   # the issue's 0.69 GB


def test_the_tails_and_the_experts_by_hand():
    # a row's tail read and written: 2 x 2 x 2048 x 2 B
    assert S.tail_step_bytes(M, 1, 2) == 16_384
    h = S.FUNCTIONS["streamed_expert_bytes"]
    assert h["peak"] == "hbm_bytes_per_s"
    # EXACT: every expert touched is held here
    assert h["work"](M, programs=0, counters={
        "experts_touched": 500}) == 500 * 18_874_368.0
    d = S.FUNCTIONS["decode_bytes"]
    assert d["peak"] == "hbm_bytes_per_s"
    assert d["work"](M, programs=2, counters={
        "experts_touched": 1000, "layer_rows": 1792}) == (
        2 * S.fixed_bytes_per_step(M, 2) + 1000 * 18_874_368.0
        + 1792 * 16_384.0)
    # the issue's step at 128 rows with every expert of 8 layers touched
    assert d["work"](M, programs=1, counters={
        "experts_touched": 512, "layer_rows": 896}) == pytest.approx(
        10.37e9, rel=0.01)


def test_the_counted_reader_prices_the_kernel_from_the_counters():
    """``trace_roofline_counted`` over a made-up trace: 512 experts through
    a kernel that took 13 ms is 512 x 18.9 MB / 819e9 / 0.013 s; a parent
    without the counter reads nothing and does not raise."""
    read = SPEC.reader("trace_roofline_counted").read
    mf = SPEC.layer_metric("moe_streamed_hbm_roofline.conv")

    class Red:
        def op_total_s(self, patterns):
            return 0.013

        def program_total_s(self, pattern):
            return 0.016

        def program_count(self, pattern):
            return 1

    ctx = {"trace": Red(), "spec": SPEC, "config": M,
           "peak": SPEC.peak("TPU v5 lite"),
           "trace_before": {"engine": {
               "moe": {"experts_touched": 1000},
               "conv": {"rows_stepped": 100}}},
           "trace_after": {"engine": {
               "moe": {"experts_touched": 1512},
               "conv": {"rows_stepped": 996}}}}
    want = 100.0 * (18_874_368.0 * 512 / 819e9) / 0.013
    assert read(ctx, mf["reader"]) == pytest.approx(want)
    assert 0 < want < 100
    whole = read(ctx, SPEC.layer_metric("decode_hbm_roofline.conv")["reader"])
    assert whole == pytest.approx(100.0 * (
        (S.fixed_bytes_per_step(M, 2) + 512 * 18_874_368.0
         + 896 * 16_384.0) / 819e9) / 0.016)
    assert 0 < whole < 100
    # a program with no conv counter (the parent): nothing, and no raise
    ctx["trace_after"] = {"engine": {"moe": {"experts_touched": 1512}}}
    assert read(ctx, SPEC.layer_metric("decode_hbm_roofline.conv")[
        "reader"]) is None
    ctx["trace_after"] = {"engine": {}}
    assert read(ctx, mf["reader"]) is None


# -- the metrics -------------------------------------------------------------

@pytest.mark.parametrize("name", CONV)
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


def test_the_cell_joins_the_thirty_six_lists_the_issue_names():
    nemotron = CELLS_BEFORE[6]
    both = [m["name"]
            for m in SPEC.bench["per_layer"][:N_LAYER_METRICS_BEFORE]
            if nemotron in m.get("workloads", ())
            and CELL in m.get("workloads", ())]
    assert sorted(both) == sorted(JOINS_SATURATED + JOINS_ROUTED + [
        "weights_s_setup", "warm_executables_s_setup"])
    assert len(both) == 36


@pytest.mark.parametrize("name", ["xla_compile_s_setup",
                                  "cache_entries_added"])
def test_the_cell_reports_what_every_cell_reports(name):
    assert "workloads" not in SPEC.metric_entry(name)
    assert name in SPEC.cell_layer_metrics(CELL)


@pytest.mark.parametrize("name", STAYS_OUT)
def test_the_cell_joins_no_list_that_prices_another_architecture(name):
    """The other ``moe_streamed_hbm_roofline.*`` and ``decode_hbm_roofline.*``
    price their own models' bytes; the ``.ssm`` and ``.kda`` lists read
    kernels and a state this model has not (their ``state_slots_peak`` twins
    read the same field as ``state_slots_peak.conv``)."""
    assert CELL not in SPEC.metric_entry(name)["workloads"]
    assert name not in SPEC.cell_layer_metrics(CELL)


def test_the_slot_peaks_twin_reads_what_the_accepted_ones_read():
    a, b = (SPEC.layer_metric(n) for n in ("state_slots_peak.conv",
                                           "state_slots_peak.ssm"))
    assert a["reader"] == b["reader"]
    assert {k: a[k] for k in a if k != "name"} == {
        k: b[k] for k in b if k != "name"}


def test_the_ops_the_shares_name_are_the_programs_own():
    from scalable_hw_agnostic_inference_tpu.ops.pallas import moe_ffn

    pat = lambda n: SPEC.layer_metric(n)["reader"]        # noqa: E731
    assert pat("moe_streamed_hbm_roofline.conv")["ops"] == [
        moe_ffn.KERNEL_NAME] == SPEC.layer_metric(
        "moe_streamed_hbm_roofline.moe")["reader"]["ops"]
    assert pat("paged_attn_share.conv")["patterns"] == [
        "paged_decode_attention"]
    import inspect

    from scalable_hw_agnostic_inference_tpu.ops.pallas import paged_attention

    assert 'name="paged_decode_attention"' in inspect.getsource(
        paged_attention)
    assert pat("decode_hbm_roofline.conv")["pattern"] == "^jit_decode"
    counters = {c for n in CONV for c in pat(n).get("counters", {}).values()}
    assert counters == {"conv.rows_stepped", "moe.experts_touched"}


def test_a_made_up_trace_gives_the_paged_kernels_share():
    red = trace.Reduced.__new__(trace.Reduced)
    red.op_s = {"paged_decode_attention": 0.0007, "fusion.7": 0.004}
    red.window_s = 0.010
    read = SPEC.reader("trace_op_share").read
    assert read({"trace": red}, SPEC.layer_metric("paged_attn_share.conv")[
        "reader"]) == pytest.approx(7.0)


def test_the_tolerance_says_what_it_refuses_and_what_it_cannot():
    ref = SPEC.reference(M["reference"]["module"])
    tol = SPEC.tolerance(M["reference"]["tolerance"])
    assert set(tol) >= {"max_abs_logprob_diff", "mean_abs_logprob_diff",
                        "top1_must_match_above_margin", "reason"}
    names = (ref.REFUSED_VARIANTS + ref.REFUSED_BY_MEAN
             + ref.NOT_REFUSED_RELIABLY)
    for name in names:
        assert name in tol["reason"], name
    assert "weights_fp8" in ref.REFUSED_BY_MEAN
    assert set(names) == {"no_conv_tail", "taps_reversed", "no_gate_b",
                          "no_qk_norm", "no_expert_bias", "weights_fp8"}


# -- the cell's dry run -------------------------------------------------------

def test_the_new_cells_dry_run_ends_correct(tmp_path):
    """Traced: the tiny conv stand-in behind the real server, the reference
    check through prefill and decode on the tails, the closed loop, the
    contract's last line with every metric of the cell that the program
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
    assert result["metrics"]["state_slots_peak.conv"]["value"] >= 1
    assert "left out" not in p.stderr
    ref = json.loads([ln for ln in lines
                      if ln.startswith("reference ")][0][10:])
    assert ref["passed"] and ref["positions"] == 8
