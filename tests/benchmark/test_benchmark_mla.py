"""What PR 32 adds to the benchmark: the Kanana-2 configuration keeps the
rules, its tiny stand-in is the program's preset, the latent step's bytes and
operations against hand-worked counts, the reader that prices them from the
program's counters, and the new cell's dry run on the CPU."""

import json
import os
import subprocess
import sys

import pytest

from benchmark import trace
from benchmark.spec import Spec
from test_benchmark_spec import assert_published_keys_unchanged

SPEC = Spec()
NAME = "kanana-2-30b-a3b-bf16"
CELL = NAME + ".decode-sat-8k"
M = SPEC.config(NAME)
S = SPEC.shapes("shapes_mla")
MLA = ["mla_decode_share.mla", "mla_decode_hbm_roofline.mla",
       "mla_decode_mxu_roofline.mla", "decode_hbm_roofline.mla",
       "latent_visible_mean.mla"]


# -- the configuration and the cell keep the rules ---------------------------

def test_the_benchmark_is_whole_with_the_new_configuration():
    assert SPEC.problems() == []
    entry = [c for c in SPEC.bench["configs"] if c["name"] == NAME][0]
    # appended, not inserted: behind the three configurations and four
    # cells that were there (not necessarily LAST: the next PR appends too)
    assert SPEC.bench["configs"][3] is entry
    assert SPEC.bench["workloads"][4]["name"] == CELL
    assert entry["reduced"] == M["reduced"] == ["num_hidden_layers"]
    assert M["published"] == {"num_hidden_layers": 48}
    assert M["model_type"] == "deepseek_v3" and M["chips"] == 1
    assert SPEC.cell_end_to_end(CELL) == ["out_tok_per_s", "setup_s"]


def test_the_published_keys_are_pinned():
    assert_published_keys_unchanged(SPEC, NAME)
    with open(os.path.join(SPEC.root, "tests", "benchmark", "data",
                           "published", NAME + ".json")) as f:
        pinned = json.load(f)
    # every width the contract names is among the pinned keys
    for key in ("hidden_size", "intermediate_size", "moe_intermediate_size",
                "kv_lora_rank", "qk_nope_head_dim", "qk_rope_head_dim",
                "qk_head_dim", "v_head_dim", "head_dim", "n_routed_experts",
                "num_experts_per_tok", "n_shared_experts", "vocab_size",
                "num_attention_heads", "routed_scaling_factor"):
        assert key in pinned, key
    assert pinned["q_lora_rank"] is None and pinned["rope_scaling"] is None


@pytest.mark.parametrize("key,value", [
    ("loop", "closed"), ("clients", 68),
    ("prompt_tokens", {"dist": "loguniform", "lo": 2048, "hi": 8192}),
    ("output_tokens", {"dist": "uniform", "lo": 1024, "hi": 2048})])
def test_the_traffic_is_the_issues(key, value):
    mix = SPEC.traffic("decode-sat-8k")
    assert mix[key] == value
    assert 45.0 <= mix["warmup_s"] <= 90.0
    # at most 4 wait behind 64 full rows: half the gate's line of 8
    assert mix["clients"] - M["engine"]["max_num_seqs"] == 4


@pytest.mark.parametrize("name", MLA)
def test_the_new_metrics_are_the_new_cells_alone(name):
    entry, mf = SPEC.metric_entry(name), SPEC.layer_metric(name)
    # the first of its list: a second latent configuration may join
    assert entry["workloads"][0] == CELL and entry["moves"] == "out_tok_per_s"
    assert name in SPEC.cell_layer_metrics(CELL)
    assert mf["layer"] in ("kernels", "model step")
    if "roofline" in name:
        assert entry["unit"] == "%" and mf["reader"]["module"] == "shapes_mla"


@pytest.mark.parametrize("name", [
    "window_skipped_share.moe", "pool_dead_share.moe",
    "decode_hbm_roofline.moe", "decode_hbm_roofline.sat"])
def test_metrics_that_price_other_models_stay_off_the_new_cell(name):
    assert name not in SPEC.cell_layer_metrics(CELL)


# -- the tiny stand-in -------------------------------------------------------

TINY_FIELDS = {
    "vocab_size": "vocab_size", "hidden_size": "dim",
    "num_hidden_layers": "n_layers", "num_attention_heads": "n_heads",
    "num_key_value_heads": "n_kv_heads", "kv_lora_rank": "kv_lora_rank",
    "qk_nope_head_dim": "qk_nope_head_dim",
    "qk_rope_head_dim": "qk_rope_head_dim", "qk_head_dim": "head_dim",
    "v_head_dim": "v_head_dim", "rope_interleave": "rope_interleave",
    "rope_scaling": "rope_scaling", "intermediate_size": "mlp_dim",
    "moe_intermediate_size": "moe_mlp_dim",
    "max_position_embeddings": "max_seq_len", "rope_theta": "rope_theta",
    "rms_norm_eps": "rms_eps", "tie_word_embeddings": "tie_embeddings",
    "first_k_dense_replace": "n_dense_layers",
    "n_routed_experts": "n_experts",
    "num_experts_per_tok": "n_experts_per_tok",
    "n_shared_experts": "n_shared_experts", "norm_topk_prob": "route_norm",
    "routed_scaling_factor": "route_scale"}


@pytest.mark.parametrize("key", sorted(TINY_FIELDS))
def test_the_tiny_stand_in_is_the_programs_preset(key):
    """``benchmark/configs/dry_run/tiny-mla.json`` against
    ``LlamaConfig.tiny_mla()`` field by field: the reference reads the
    file, the dry run serves the preset."""
    from scalable_hw_agnostic_inference_tpu.models.llama import LlamaConfig

    tiny = SPEC.dry_run_model("tiny-mla")
    assert set(tiny) - {"what", "q_lora_rank", "scoring_func"} == set(
        TINY_FIELDS)
    assert tiny["q_lora_rank"] is None and tiny["scoring_func"] == "sigmoid"
    assert tiny[key] == getattr(LlamaConfig.tiny_mla(), TINY_FIELDS[key])
    assert M["dry_run"] == {"model_id": "tiny-mla", "model": "tiny-mla"}


# -- bytes and operations, by hand ------------------------------------------

def test_parameters_by_hand():
    # q 2048 x 32 x 192 = 12,582,912; kv_a 2048 x 576 = 1,179,648;
    # kv_b 512 x 32 x 256 = 4,194,304; o 4096 x 2048 = 8,388,608
    assert S.attention_params(M) == (12_582_912 + 1_179_648 + 4_194_304
                                     + 8_388_608) == 26_345_472
    assert S.expert_params(M) == 3 * 2048 * 768 == 4_718_592
    assert M["memory"]["table"]["one routed expert"] == 9_437_184
    assert S.latent_values(M) == 576
    assert M["memory"]["weights_bytes"] == 8_859_156_480


def test_what_a_step_reads_whatever_it_routed():
    # seven layers' attention, one dense MLP (3 x 2048 x 6144 = 37,748,736),
    # six shared MLPs of two experts' width, the head (2048 x 128256 =
    # 262,668,288), in bf16; six float32 routers of 2048 x 128
    params = 7 * 26_345_472 + 37_748_736 + 6 * 2 * 4_718_592 + 262_668_288
    assert params == 541_458_432
    assert S.fixed_bytes_per_step(M, 2) == 2 * 541_458_432 + 6 * 1_048_576 \
        == 1_089_208_320


def test_kernel_work_by_hand():
    # 10 steps of 64 rows at 5,000 tokens in 7 layers
    rows = 10 * 64 * 5000 * 7
    assert S.latent_bytes(M, rows, 2) == rows * 1152
    # scores over 576 lanes and the sum over 512, 32 heads, 2 ops each
    assert S.latent_flops(M, rows) == rows * 2 * 32 * 1088 == rows * 69_632
    # 60 operations a byte where the chip's ridge is 240: the bytes bound it
    assert (S.latent_flops(M, rows) / 197e12
            < S.latent_bytes(M, rows, 2) / 819e9)
    got = S.decode_bytes(M, steps=10, experts_touched=10 * 6 * 120,
                         tokens_visible=rows, weight_bytes=2, kv_bytes=2)
    assert got == (10 * 1_089_208_320 + 7200 * 9_437_184 + rows * 1152)
    # 10.5 GB a step: 12.8 ms at 819 GB/s; experts 65%, latents 25%
    assert got / 10 / 819e9 == pytest.approx(12.8e-3, rel=5e-3)
    assert 7200 * 9_437_184 / got == pytest.approx(0.648, abs=0.005)
    assert rows * 1152 / got == pytest.approx(0.246, abs=0.005)


# -- the reader --------------------------------------------------------------

def _ctx(after_engine):
    tr = {"planes": {
        "/device:TPU:0": {
            "XLA Modules": [["jit_decode(11)", 1000, 4000],
                            ["jit_prefill(12)", 7000, 2000]],
            "XLA Ops": [["%fusion.1", 1000, 1000],
                        ["%mla_paged_decode.3", 2000, 1500],
                        ["%mla_paged_decode.9", 3500, 500],
                        ["%ragged-dot.2", 4000, 1000]]},
        "/host:CPU": {"bench-tracer": [["bench_window", 0, 10000]]}}}
    red = trace.Reduced(tr)
    return {"spec": SPEC, "config": M, "peak": SPEC.peak("TPU v5 lite"),
            "trace": red, "trace_before": {"engine": {}},
            "trace_after": {"engine": after_engine}}, red


def test_the_counted_reader_prices_one_ops_time_and_a_programs():
    ctx, red = _ctx({"mla": {"tokens_visible": 7_000, "layer_steps": 7},
                     "moe": {"experts_touched": 600}})
    assert red.op_total_s(["mla_paged_decode"]) == pytest.approx(2e-6)
    want = {
        "mla_decode_hbm_roofline.mla": 7_000 * 1152 / 819e9 / 2e-6,
        "mla_decode_mxu_roofline.mla": 7_000 * 69_632 / 197e12 / 2e-6,
        "decode_hbm_roofline.mla": (1_089_208_320 + 600 * 9_437_184
                                    + 7_000 * 1152) / 819e9 / 4e-6}
    for name, share in want.items():
        mf = SPEC.layer_metric(name)
        assert SPEC.reader(mf["reader"]["kind"]).read(
            ctx, mf["reader"]) == pytest.approx(100.0 * share, rel=1e-12)
    mf = SPEC.layer_metric("mla_decode_share.mla")
    assert SPEC.reader(mf["reader"]["kind"]).read(
        ctx, mf["reader"]) == pytest.approx(20.0)


@pytest.mark.parametrize("engine", [
    {},                                           # the parent: no counters
    {"moe": {"experts_touched": 3}},              # a routed parent
], ids=["no-counters", "no-latent-counter"])
@pytest.mark.parametrize("name", [m for m in MLA if "roofline" in m])
def test_the_counted_reader_reads_nothing_from_a_program_without_the_counter(
        name, engine):
    mf = SPEC.layer_metric(name)
    read = SPEC.reader(mf["reader"]["kind"]).read
    ctx, _ = _ctx(engine)
    assert read(ctx, mf["reader"]) is None
    ctx["trace"] = None
    assert read(ctx, mf["reader"]) is None


def test_the_counted_reader_reads_nothing_where_the_trace_has_no_such_op():
    ctx, _ = _ctx({"mla": {"tokens_visible": 7_000}})
    mf = SPEC.layer_metric("mla_decode_hbm_roofline.mla")
    params = {**mf["reader"], "ops": ["no_such_kernel"]}
    assert SPEC.reader(mf["reader"]["kind"]).read(ctx, params) is None


def test_latent_visible_mean_by_hand():
    mf = SPEC.layer_metric("latent_visible_mean.mla")
    read = SPEC.reader(mf["reader"]["kind"]).read
    bare = {"engine": {"steps": 5}}
    assert read({"before": bare, "after": bare}, mf["reader"]) is None
    # 10 steps x 7 layers, 64 slots at 5,000 tokens each
    after = {"engine": {"mla": {"layer_steps": 70,
                                "tokens_visible": 70 * 64 * 5000}}}
    assert read({"before": {"engine": {}}, "after": after},
                mf["reader"]) == pytest.approx(5000.0)


# -- the cell's dry run -------------------------------------------------------

def test_the_new_cells_dry_run_ends_correct(tmp_path):
    """Untraced (``test_benchmark_e2e.py`` runs the traced one): the tiny
    latent stand-in behind the real server, the reference check through
    prefill and absorbed decode, the closed loop, the contract's last line."""
    p = subprocess.run(
        [sys.executable, os.path.join(SPEC.root, "benchmark", "run.py"),
         "--workload", CELL, "--seed", "3000000019", "--seconds", "2",
         "--trace", "0", "--dry-run", "--out", str(tmp_path / "out")],
        env={**os.environ, "JAX_PLATFORMS": "cpu"}, cwd=SPEC.root,
        capture_output=True, text=True, timeout=600)
    assert p.returncode == 0, p.stderr[-3000:]
    lines = p.stdout.strip().splitlines()
    result = json.loads(lines[-1])
    assert result["correct"] is True and result["failed"] == 0
    assert result["attempted"] > 0
    assert list(result["metrics"]) == ["out_tok_per_s", "setup_s"]
    ref = json.loads([ln for ln in lines
                      if ln.startswith("reference ")][0][10:])
    assert ref["passed"] and ref["positions"] == 8
