"""What PR 34 adds to the benchmark: the Kimi-Linear configuration keeps the
rules (and everything that was there is still there, in its order, before
it), its published keys are pinned, the traffic is the issue's, the stage's
operations and the recurrent layers' work against hand-worked counts, the
``.kda`` metrics are the new cell's alone among the cells that were there
(the twins of ``.prefill`` metrics read what those read), Kanana's entries
are where they were, and the new cell's dry run on the CPU."""

import json
import os
import subprocess
import sys

import pytest

from benchmark import trace
from benchmark.spec import Spec
from test_benchmark_spec import assert_published_keys_unchanged

SPEC = Spec()
NAME = "kimi-linear-48b-a3b-bf16-ep2"
CELL = NAME + ".prefill-rate-16k"
M = SPEC.config(NAME)
S = SPEC.shapes("shapes_kda")
KDA = ["kda_prefill_share.kda", "kda_decode_share.kda",
       "kda_prefill_mxu_roofline.kda", "kda_decode_hbm_roofline.kda",
       "prefill_mxu_roofline.kda", "state_carry_per_request.kda",
       "state_slots_peak.kda", "mla_attention_share.kda",
       "moe_ffn_share.kda", "loop_idle_share.kda", "device_stall_share.kda",
       "prefill_programs_per_request.kda", "waiting_peak.kda",
       "intake_wait_mean_ms.kda"]
#: the decode side moves what a decoding row feels, the rest the first token
MOVES_GAP = ["kda_decode_share.kda", "kda_decode_hbm_roofline.kda"]
#: what the accepted benchmark held before this PR, in its order
CONFIGS_BEFORE = ["mistral-7b-int8", "mistral-7b-bf16-tp4",
                  "trinity-mini-bf16", "kanana-2-30b-a3b-bf16"]
CELLS_BEFORE = ["mistral-7b-int8.decode-sat", "mistral-7b-int8.prefill-rate",
                "mistral-7b-bf16-tp4.decode-sat",
                "trinity-mini-bf16.decode-sat-4k",
                "kanana-2-30b-a3b-bf16.decode-sat-8k"]
N_LAYER_METRICS_BEFORE = 62


# -- the configuration and the cell keep the rules ---------------------------

def test_the_benchmark_is_whole_and_what_was_there_comes_first():
    """Appended, not inserted: every accepted configuration, cell and
    per-layer metric is where it was, and this PR's come behind them (not
    necessarily LAST: the next PR appends too)."""
    assert SPEC.problems() == []
    b = SPEC.bench
    assert [c["name"] for c in b["configs"]][:4] == CONFIGS_BEFORE
    assert [w["name"] for w in b["workloads"]][:5] == CELLS_BEFORE
    names = [m["name"] for m in b["per_layer"]]
    assert names[N_LAYER_METRICS_BEFORE - 2:N_LAYER_METRICS_BEFORE] == [
        "moe_streamed_share.moe", "moe_streamed_hbm_roofline.moe"]
    assert names[N_LAYER_METRICS_BEFORE:N_LAYER_METRICS_BEFORE + 14] == KDA
    assert b["configs"][4]["name"] == NAME
    assert b["workloads"][5]["name"] == CELL and b["workloads"][5][
        "chips"] == 1
    assert b["configs"][4]["reduced"] == M["reduced"]
    assert M["model_type"] == "kimi_linear" and M["chips"] == 1
    # ISSUE 34's rule: ``gap_p95_ms`` is judged end to end unless one of
    # the builder's two sets of six spreads over 1.5%; none did (PERF.md
    # section 6), so the cell joins its list and the decode step's behind
    # the accepted cell
    assert SPEC.cell_end_to_end(CELL) == ["ttft_p90_ms", "gap_p95_ms",
                                          "setup_s"]
    for name in ("gap_p95_ms", "decode_step_ms.prefill"):
        assert SPEC.metric_entry(name)["workloads"][:2] == [
            "mistral-7b-int8.prefill-rate", CELL]
    assert len(b["workloads"][5]["why"]) <= 200


def test_what_the_position_pins_hid_still_holds():
    """What PR 32's test pinned by position (``configs[-1]``,
    ``workloads[-1]``), as what it meant: Kanana's entries where they were,
    this PR's behind them."""
    kanana = "kanana-2-30b-a3b-bf16"
    cell = kanana + ".decode-sat-8k"
    m = SPEC.config(kanana)
    entry = [c for c in SPEC.bench["configs"] if c["name"] == kanana][0]
    assert SPEC.bench["configs"][3] is entry
    assert SPEC.bench["workloads"][4]["name"] == cell
    assert entry["reduced"] == m["reduced"] == ["num_hidden_layers"]
    assert m["published"] == {"num_hidden_layers": 48}
    assert m["model_type"] == "deepseek_v3" and m["chips"] == 1
    assert SPEC.cell_end_to_end(cell) == ["out_tok_per_s", "setup_s"]


def test_the_published_keys_are_pinned():
    assert_published_keys_unchanged(SPEC, NAME)
    with open(os.path.join(SPEC.root, "tests", "benchmark", "data",
                           "published", NAME + ".json")) as f:
        pinned = json.load(f)
    for key in ("hidden_size", "intermediate_size", "moe_intermediate_size",
                "kv_lora_rank", "qk_nope_head_dim", "qk_rope_head_dim",
                "v_head_dim", "head_dim", "num_experts_per_token",
                "num_shared_experts", "vocab_size", "num_attention_heads",
                "routed_scaling_factor", "model_max_length"):
        assert key in pinned, key
    lin = pinned["linear_attn_config"]
    assert (lin["head_dim"], lin["num_heads"],
            lin["short_conv_kernel_size"]) == (128, 32, 4)
    assert pinned["q_lora_rank"] is None and pinned["mla_use_nope"] is True
    # what was cut stands beside what was published
    pub = M["published"]
    assert (pub["num_hidden_layers"], pub["num_experts"]) == (27, 256)
    assert len(pub["linear_attn_config"]["kda_layers"]) == 20
    assert pub["linear_attn_config"]["full_attn_layers"] == [
        4, 8, 12, 16, 20, 24, 27]
    assert M["reduced"] == ["num_hidden_layers", "num_experts",
                            "linear_attn_config"]


@pytest.mark.parametrize("key,value", [
    ("loop", "open"), ("arrival", {"process": "poisson"}),
    ("prompt_tokens", {"dist": "loguniform", "lo": 4096, "hi": 16384}),
    ("output_tokens", {"dist": "uniform", "lo": 32, "hi": 128}),
    ("warmup_s", 8.0)])
def test_the_traffic_is_the_issues(key, value):
    mix = SPEC.traffic("prefill-rate-16k")
    assert mix[key] == value
    assert 0.3 <= mix["rate_per_s"] <= 3.0
    assert "knee" in mix["rate_found"] and "sweep" in mix["rate_found"]
    # every prompt fits the engine with its answer, and walks 2 to 8 programs
    eng = M["engine"]
    assert mix["prompt_tokens"]["hi"] + mix["output_tokens"]["hi"] <= eng[
        "max_model_len"]
    assert mix["prompt_tokens"]["lo"] // max(
        eng["context_encoding_buckets"]) == 2
    assert mix["prompt_tokens"]["hi"] // max(
        eng["context_encoding_buckets"]) == 8


def test_the_memory_table_is_the_issues_arithmetic():
    mem = M["memory"]
    t = mem["table"]
    stage = [v for k, v in t.items() if k.startswith("this stage")][0]
    assert stage == mem["weights_bytes"]
    assert stage == pytest.approx(9.32e9, rel=0.01)
    kda_l, mla_l = [v for k, v in t.items()
                    if k.startswith("one expert layer, 128")][0]
    dense = [v for k, v in t.items() if k.startswith("the leading")][0]
    vocab = [v for k, v in t.items() if k.startswith("embedding")][0]
    assert 3 * kda_l + mla_l + dense + vocab == stage
    assert kda_l == pytest.approx(1.906e9, rel=2e-3)
    assert mla_l == pytest.approx(1.886e9, rel=2e-3)
    whole = [v for k, v in t.items() if k.startswith("the whole")][0]
    assert whole == pytest.approx(98.2e9, rel=2e-3)
    assert mem["sum_bytes"] == (mem["weights_bytes"] + mem["kv_pool_bytes"]
                                + mem["state_arena_bytes"])
    assert mem["sum_bytes"] > 0.25 * 16 * 2 ** 30     # the driver's floor


# -- the arithmetic ----------------------------------------------------------

def test_a_token_slots_products_by_hand():
    # KDA attention: q, k, v and o 2304 x 4096 each; two low-rank pairs
    # 2304 x 128 + 128 x 4096; beta 2304 x 32
    kda = 4 * 2304 * 4096 + 2 * (2304 * 128 + 128 * 4096) + 2304 * 32
    assert S.kda_attention_params(M) == kda == 39_460_864
    mla = 2304 * 6144 + 2304 * 576 + 512 * 8192 + 4096 * 2304
    assert S.mla_attention_params(M) == mla == 29_114_368
    assert S.expert_params(M) == 3 * 2304 * 1024 == 7_077_888
    assert S.held_assignments_per_token(M) == 4.0     # 8 x 128 / 256
    per_token = (4 * kda + mla + 3 * 2304 * 9216
                 + 4 * (2304 * 256 + (1 + 4.0) * 7_077_888))
    assert S.product_params_per_token(M) == per_token
    # the issue's 0.79 GFLOP of products a token on this stage
    assert 2 * per_token == pytest.approx(0.79e9, rel=0.01)
    work = S.FUNCTIONS["prefill_flops"]
    assert work["peak"] == "bf16_flops_per_s"
    assert work["work"](M, programs=3, real=5000, pad=1144, chips=1) == (
        2.0 * per_token * 6144)


def test_the_recurrence_and_the_state_by_hand():
    # 6 d^2 operations a token and head: 32 heads of 128
    assert S.recurrence_flops(M, 1) == 6 * 32 * 128 * 128 == 3_145_728
    f = S.FUNCTIONS["kda_recurrence_flops"]
    assert f["peak"] == "bf16_flops_per_s"
    assert f["work"](M, programs=9, counters={"layer_tokens": 8192}) == (
        3_145_728.0 * 8192)
    # a row's state read and written: 2 x 32 x 128 x 128 x 4 B
    assert S.state_step_bytes(M, 1) == 2 * 2_097_152
    g = S.FUNCTIONS["kda_state_bytes"]
    assert g["peak"] == "hbm_bytes_per_s"
    assert g["work"](M, programs=2, counters={"layer_rows": 24}) == (
        24 * 4_194_304.0)


def test_the_counted_reader_prices_the_kernels_from_the_counters():
    """``trace_roofline_counted`` over a made-up trace: 8,192 layer-tokens
    through a chunk kernel that took 10 ms is 8192 x 3.15 MFLOP / 197e12 /
    0.01 s; a parent without the counter reads nothing."""
    read = SPEC.reader("trace_roofline_counted").read
    mf = SPEC.layer_metric("kda_prefill_mxu_roofline.kda")

    class Red:
        def op_total_s(self, patterns):
            assert patterns == ["kda_chunk_prefill"]
            return 0.010

    ctx = {"trace": Red(), "spec": SPEC, "config": M,
           "peak": SPEC.peak("TPU v5 lite"),
           "trace_before": {"engine": {"kda": {"prefill_tokens": 1000}}},
           "trace_after": {"engine": {"kda": {"prefill_tokens": 9192}}}}
    want = 100.0 * (3_145_728.0 * 8192 / 197e12) / 0.010
    assert read(ctx, mf["reader"]) == pytest.approx(want)
    assert 0 < want < 100
    ctx["trace_after"] = {"engine": {}}
    assert read(ctx, mf["reader"]) is None


# -- the metrics -------------------------------------------------------------

@pytest.mark.parametrize("name", KDA)
def test_the_new_metrics_are_the_new_cells_alone(name):
    """Alone among the cells that were there; the first of its list, behind
    which a second configuration of the family may join."""
    entry, mf = SPEC.metric_entry(name), SPEC.layer_metric(name)
    assert entry["workloads"][0] == CELL
    assert entry["moves"] == mf["moves"] == (
        "gap_p95_ms" if name in MOVES_GAP else "ttft_p90_ms")
    assert name in SPEC.cell_layer_metrics(CELL)
    for w in CELLS_BEFORE:
        assert name not in SPEC.cell_layer_metrics(w)
    if "roofline" in name:
        assert entry["unit"] == "%" and entry["better"] == "higher"


@pytest.mark.parametrize("twin,of", [
    ("loop_idle_share.kda", "loop_idle_share.prefill"),
    ("device_stall_share.kda", "device_stall_share.prefill"),
    ("prefill_programs_per_request.kda",
     "prefill_programs_per_request.prefill"),
    ("waiting_peak.kda", "waiting_peak.prefill"),
    ("intake_wait_mean_ms.kda", "intake_wait_mean_ms.prefill")])
def test_a_twin_reads_what_the_pinned_metric_reads(twin, of):
    """Five ``.prefill`` metrics' cell lists were held whole by an accepted
    test when this cell came, so it reports twins, as ISSUE 34 says: the
    same reader, parameters, layer, unit and direction under a ``.kda``
    name. Every other ``.prefill`` metric whose reader knows no architecture
    takes the cell's name behind its own. The twins stay (the ledger's
    series carry their names): those five lists start with their one cell
    and do not hold this one."""
    a, b = SPEC.layer_metric(twin), SPEC.layer_metric(of)
    assert {k: v for k, v in a.items() if k != "name"} == {
        k: v for k, v in b.items() if k != "name"}
    assert SPEC.metric_entry(of)["workloads"][0] == (
        "mistral-7b-int8.prefill-rate")
    assert CELL not in SPEC.metric_entry(of)["workloads"]


@pytest.mark.parametrize("name", [
    "queue_wait_mean_ms.prefill", "waiting_max.prefill",
    "pad_fraction_prefill.prefill", "prefill_step_ms.prefill",
    "device_idle_share.prefill", "ttft_p50_ms.prefill",
    "decode_step_ms.prefill", "shed_share.prefill",
    "gen_late_p99_ms.prefill", "weights_s_setup",
    "warm_executables_s_setup", "xla_compile_s_setup",
    "cache_entries_added"])
def test_the_cell_joins_the_metrics_that_know_no_architecture(name):
    assert name in SPEC.cell_layer_metrics(CELL)
    cells = SPEC.metric_entry(name).get("workloads")
    # appended behind: only cells that were there stand before it
    assert cells is None or set(cells[:cells.index(CELL)]) <= set(
        CELLS_BEFORE)


def test_the_ops_the_shares_name_are_the_programs_own():
    from scalable_hw_agnostic_inference_tpu.ops import mla, moe
    from scalable_hw_agnostic_inference_tpu.ops.pallas import (
        kda_chunk,
        kda_step,
        mla_paged_attention,
    )

    pat = lambda n: SPEC.layer_metric(n)["reader"]        # noqa: E731
    assert pat("kda_prefill_share.kda")["patterns"] == [
        kda_chunk.KERNEL_NAME] == pat("kda_prefill_mxu_roofline.kda")["ops"]
    assert pat("kda_decode_share.kda")["patterns"] == [
        kda_step.KERNEL_NAME] == pat("kda_decode_hbm_roofline.kda")["ops"]
    assert set(pat("mla_attention_share.kda")["patterns"]) == {
        "flash_attention", mla.EXPAND_NAME, mla.ABSORB_NAME,
        mla_paged_attention.KERNEL_NAME}
    assert moe.GROUPED_NAME in pat("moe_ffn_share.kda")["patterns"]
    assert pat("moe_ffn_share.kda") == SPEC.layer_metric(
        "moe_ffn_share.moe")["reader"]


def test_a_made_up_trace_gives_the_kernels_shares():
    """``trace_op_share`` finds the two kernels by their names in a trace
    made up by hand: 3 ms of chunk kernel and 1 ms of step kernel in a
    10 ms window."""
    red = trace.Reduced.__new__(trace.Reduced)
    red.op_s = {"kda_chunk_prefill": 0.003, "kda_decode_step": 0.001,
                "fusion.7": 0.004}
    red.window_s = 0.010
    read = SPEC.reader("trace_op_share").read
    ctx = {"trace": red}
    assert read(ctx, SPEC.layer_metric("kda_prefill_share.kda")[
        "reader"]) == pytest.approx(30.0)
    assert read(ctx, SPEC.layer_metric("kda_decode_share.kda")[
        "reader"]) == pytest.approx(10.0)


# -- the cell's dry run -------------------------------------------------------

def test_the_new_cells_dry_run_ends_correct(tmp_path):
    """Traced: the tiny KDA stand-in behind the real server, the reference
    check through prefill and recurrent decode, the open loop, the
    contract's last line with every ``.kda`` metric the program (not the
    device) gives."""
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
    want = {n for n in KDA
            if SPEC.metric_entry(n)["source"] != "device_trace"}
    assert want <= set(result["metrics"]), want - set(result["metrics"])
    assert result["metrics"]["state_slots_peak.kda"]["value"] >= 1
    assert "left out" not in p.stderr
    ref = json.loads([ln for ln in lines
                      if ln.startswith("reference ")][0][10:])
    assert ref["passed"] and ref["positions"] == 8
