"""What PR 33 adds to the benchmark: two per-layer metrics of the streamed
expert product as data, the shape file that prices an expert touched, and
both against hand-worked counts. Nothing that was there is edited: the
entries are appended to ``BENCHMARK.json`` and to both routed cells."""

import pytest

from benchmark import trace
from benchmark.spec import Spec

SPEC = Spec()
S = SPEC.shapes("shapes_moe")
CELLS = ["trinity-mini-bf16.decode-sat-4k",
         "kanana-2-30b-a3b-bf16.decode-sat-8k"]
KANANA, TRINITY = (SPEC.config("kanana-2-30b-a3b-bf16"),
                   SPEC.config("trinity-mini-bf16"))
NEW = ["moe_streamed_share.moe", "moe_streamed_hbm_roofline.moe"]
#: the cells the benchmark had when these metrics came, the routed two among
#: them: a later routed cell may join behind ``CELLS``
KNOWN = ["mistral-7b-int8.decode-sat", "mistral-7b-int8.prefill-rate",
         "mistral-7b-bf16-tp4.decode-sat"] + CELLS


def test_the_benchmark_is_whole_with_the_new_metrics():
    assert SPEC.problems() == []
    names = [m["name"] for m in SPEC.bench["per_layer"]]
    first = names.index(NEW[0])
    # appended behind the last entry of the PR before, and together (not
    # necessarily LAST: the next PR appends too)
    assert names[first - 1] == "latent_visible_mean.mla"
    assert names[first:first + 2] == NEW


@pytest.mark.parametrize("name", NEW)
def test_the_new_metrics_are_the_routed_cells_alone(name):
    entry, mf = SPEC.metric_entry(name), SPEC.layer_metric(name)
    assert entry["workloads"][:2] == CELLS
    assert entry["moves"] == mf["moves"] == "out_tok_per_s"
    assert entry["layer"] == mf["layer"] == "kernels"
    assert entry["unit"] == "%" and entry["better"] == "higher"
    for cell in CELLS:
        assert name in SPEC.cell_layer_metrics(cell)
    for w in KNOWN:
        if w not in CELLS:
            assert name not in SPEC.cell_layer_metrics(w)


@pytest.mark.parametrize("cfg,mb", [(KANANA, 9_437_184), (TRINITY, 12_582_912)],
                         ids=["kanana-9.44MB", "trinity-12.58MB"])
def test_an_expert_touched_by_hand(cfg, mb):
    """Three matrices of ``hidden_size x moe_intermediate_size`` in the
    deployment's weight type, from the published keys: 3 x 2048 x 768 x 2
    and 3 x 2048 x 1024 x 2."""
    assert S.expert_bytes(cfg, cfg["bytes"]["weight"]) == mb
    assert mb == 3 * cfg["hidden_size"] * cfg["moe_intermediate_size"] * 2
    priced = SPEC.priced(SPEC.layer_metric(NEW[1])["reader"])
    assert priced["peak"] == "hbm_bytes_per_s"
    assert priced["work"](cfg, programs=0,
                          counters={"experts_touched": 684}) == 684 * mb


def _ctx(after_engine, kernel="%moe_grouped_ffn_streamed"):
    tr = {"planes": {
        "/device:TPU:0": {
            "XLA Modules": [["jit_decode(11)", 1000, 6000]],
            "XLA Ops": [["%fusion.1", 1000, 1000],
                        [kernel + ".3", 2000, 1500],
                        [kernel + ".9", 3500, 500],
                        ["%mla_paged_decode.2", 4000, 1000],
                        ["%ragged-dot.4", 5000, 2000]]},
        "/host:CPU": {"bench-tracer": [["bench_window", 0, 10000]]}}}
    return {"spec": SPEC, "config": KANANA,
            "peak": SPEC.peak("TPU v5 lite"), "trace": trace.Reduced(tr),
            "trace_before": {"engine": {}},
            "trace_after": {"engine": after_engine}}


def test_the_roofline_prices_the_kernels_own_time():
    # one step's six expert layers at 114 experts each, in 2 us of kernel
    ctx = _ctx({"moe": {"experts_touched": 684, "layer_steps": 6}})
    mf = SPEC.layer_metric(NEW[1])
    got = SPEC.reader(mf["reader"]["kind"]).read(ctx, mf["reader"])
    assert got == pytest.approx(100.0 * 684 * 9_437_184 / 819e9 / 2e-6,
                                rel=1e-12)
    # the whole expert product still reads both forms: 2 + 2 us of the
    # 10 us window
    share = SPEC.layer_metric("moe_ffn_share.moe")
    assert SPEC.reader(share["reader"]["kind"]).read(
        ctx, share["reader"]) == pytest.approx(100.0 * 4 / 10)


@pytest.mark.parametrize("engine,kernel", [
    ({}, "%moe_grouped_ffn_streamed"),                # no routed counters
    ({"moe": {"experts_touched": 684}}, "%ragged-dot"),   # the parent
], ids=["no-counters", "no-such-op"])
def test_the_roofline_reads_nothing_from_the_parent(engine, kernel):
    mf = SPEC.layer_metric(NEW[1])
    read = SPEC.reader(mf["reader"]["kind"]).read
    assert read(_ctx(engine, kernel), mf["reader"]) is None


def test_the_streamed_share_by_hand():
    mf = SPEC.layer_metric(NEW[0])
    read = SPEC.reader(mf["reader"]["kind"]).read
    bare = {"engine": {"steps": 5}}
    assert read({"before": bare, "after": bare}, mf["reader"]) is None
    before = {"engine": {"moe": {"layer_steps": 60,
                                 "streamed_layer_steps": 60}}}
    after = {"engine": {"moe": {"layer_steps": 180,
                                "streamed_layer_steps": 150}}}
    assert read({"before": before, "after": after},
                mf["reader"]) == pytest.approx(75.0)
    # the parent's program keeps the group and not the key: it reads 0
    parent = {"engine": {"moe": {"layer_steps": 180}}}
    assert read({"before": {"engine": {}}, "after": parent},
                mf["reader"]) == 0.0
