"""Offline perf model (VERDICT r4 #1): deviceless AOT compile + roofline.

The projection math is pure and pinned exactly; the topology compile test
runs a REAL (tiny-geometry) workload against the v5e topology — the same
code path that produces PERF_MODEL.json — and skips only if this
environment's TPU plugin cannot build a deviceless topology at all.
"""

import pytest

from scalable_hw_agnostic_inference_tpu.perf import model as pm
from scalable_hw_agnostic_inference_tpu.perf import topo


_TOPO_OK = None


def _require_topology() -> None:
    """Runtime (NOT collection-time) topology probe. Building the v5e
    topology desc takes minutes on some containers; as an eager
    ``skipif(...)`` argument that cost was charged to every tier-1 run at
    collection, even with all topology tests deselected as ``slow``.
    Probed once per process, then cached."""
    global _TOPO_OK
    if _TOPO_OK is None:
        try:
            topo.topology_devices(1)
            _TOPO_OK = True
        except Exception:
            _TOPO_OK = False
    if not _TOPO_OK:
        pytest.skip("no deviceless TPU topology support here")


# ---------------------------------------------------------------------------
# pure math
# ---------------------------------------------------------------------------

def test_roofline_bound_selection():
    hw = {"bf16_flops": 100.0, "hbm_bytes_s": 10.0}
    r = pm.roofline(50.0, 1.0, hw)          # compute 0.5s > memory 0.1s
    assert r["bound"] == "mxu" and r["t_roofline_s"] == 0.5
    assert r["mfu_ceiling"] == 1.0
    r = pm.roofline(10.0, 5.0, hw)          # memory 0.5s > compute 0.1s
    assert r["bound"] == "hbm" and r["t_roofline_s"] == 0.5
    assert r["mfu_ceiling"] == pytest.approx(0.2)


def _fake_rows():
    # sd step 10ms roofline, vae 5ms; llama prefill 20ms, decode 1ms
    def row(t, flops=1e12, bytes_=1e9, opt=None, batch=8):
        return {"t_roofline_s": t, "flops": flops, "bytes_accessed": bytes_,
                "optimal_seconds": opt or t * 0.5, "batch": batch,
                "family": "x", "work_unit": "u", "t_mxu_s": t * 0.4,
                "t_hbm_s": t, "bound": "hbm", "compile_s": 1.0}

    rows = {"sd_step_b1": row(0.010), "sd_vae_b1": row(0.005),
            "sd_step_b4": row(0.020), "sd_vae_b4": row(0.008),
            "llama1b_prefill": row(0.020), "llama1b_decode": row(0.001)}
    for r in rows.values():
        r["family"] = "sd" if "sd" in repr(r) else "x"
    rows["sd_step_b1"]["family"] = rows["sd_vae_b1"]["family"] = "sd"
    return rows


def test_compose_multiplies_scan_trip_counts():
    rows = _fake_rows()
    composed = pm.compose(rows)
    # sd: 25 steps x 10ms + 5ms = 255ms
    assert composed["sd_b1"]["t_roofline_s"] == pytest.approx(0.255)
    assert composed["sd_b4"]["t_roofline_s"] == pytest.approx(
        25 * 0.020 + 0.008)
    # llama: prefill + 128 x decode; TTFT/TPOT split recorded
    gen = composed["llama1b_gen"]
    assert gen["t_roofline_s"] == pytest.approx(0.020 + 128 * 0.001)
    assert gen["ttft_roofline_s"] == pytest.approx(0.020)
    assert gen["tpot_roofline_s"] == pytest.approx(0.001)
    assert gen["work"] == 8 * 128


def test_calibration_and_projection():
    rows = _fake_rows()
    composed = pm.compose(rows)
    measured = {"sd_b1": {"seconds": 0.510, "source": "test"}}
    cal = pm.calibrate_eta(composed, measured=measured)
    assert cal["eta_roofline"] == pytest.approx(0.5)
    proj = pm.project(composed, cal)
    # projected = roofline / eta; sd_b4: 0.508 / 0.5 = 1.016s -> ~3.94 img/s
    assert proj["sd_b4"]["projected_s_per_call"] == pytest.approx(1.016)
    assert proj["sd_b4"]["projected_per_s"] == pytest.approx(4 / 1.016)
    # ceiling is the pure roofline rate
    assert proj["sd_b1"]["ceiling_per_s"] == pytest.approx(1 / 0.255)
    # $-ratio vs inf2 attached to the sd family
    assert "projected_per_dollar_vs_inf2" in proj["sd_b4"]


def test_projection_without_anchor_gives_ceiling_only():
    rows = _fake_rows()
    composed = pm.compose(rows)
    proj = pm.project(composed, None)
    assert "projected_per_s" not in proj["sd_b1"]
    assert proj["sd_b1"]["ceiling_per_s"] > 0


def test_render_md_contains_the_north_star_math():
    rows = _fake_rows()
    composed = pm.compose(rows)
    cal = pm.calibrate_eta(
        composed, measured={"sd_b1": {"seconds": 0.51, "source": "test"}})
    res = {"hw": pm.V5E, "inf2": pm.INF2, "north_star_ratio": 2.0,
           "platform": "t", "jax": "x", "calibration": cal,
           "components": rows, "composed": composed,
           "projections": pm.project(composed, cal), "errors": {}}
    md = pm.render_md(res)
    assert "4.72 img/s/chip" in md          # 2x inf2/$ scaled to v5e $/hr
    assert "eta = 0.500" in md
    assert "sd_b4" in md and "llama1b_gen" in md


# ---------------------------------------------------------------------------
# the real compile path (deviceless topology)
# ---------------------------------------------------------------------------

@pytest.mark.slow  # tier-1 budget: see scripts/check_tier1_budget.py
def test_tiny_workload_compiles_against_v5e_topology():
    _require_topology()
    row = pm.run_workload("sd_tiny", lambda: pm.wl_sd_step(1, tiny=True),
                          verbose=False)
    assert row["flops"] > 0 and row["bytes_accessed"] > 0
    assert row["bound"] in ("mxu", "hbm")
    assert row["t_roofline_s"] > 0
    # XLA:TPU's own latency estimate comes back with the executable
    assert row["optimal_seconds"] is None or row["optimal_seconds"] > 0
    # the split-VAE variant: the lax.map body is counted once by XLA, so
    # run_workload must scale by the declared trip count
    fused = pm.run_workload("vae_tiny", lambda: pm.wl_sd_vae(2, tiny=True),
                            verbose=False)
    split = pm.run_workload("vae_tiny_split",
                            lambda: pm.wl_sd_vae(2, tiny=True, split=True),
                            verbose=False)
    assert split["flops"] > 0
    # trip-scaled: split ~ 2x the single-image body, same order as fused
    assert 0.2 < split["flops"] / max(fused["flops"], 1) < 5


@pytest.mark.slow  # tier-1 budget: see scripts/check_tier1_budget.py
def test_flux_tp8_tiny_lowers_on_8dev_topology_mesh():
    _require_topology()
    row = pm.run_workload("flux_tiny", lambda: pm.wl_flux_tp8(tiny=True),
                          verbose=False)
    assert row["n_devices"] == 8
    assert row["flops"] > 0


@pytest.mark.slow  # tier-1 budget: see scripts/check_tier1_budget.py
def test_paged_decode_tiny_lowers_for_tpu():
    """The REAL Pallas paged kernel must lower for the TPU target (it runs
    interpret-mode everywhere else in CI — a Mosaic tiling violation in its
    BlockSpecs once survived to this round because nothing compiled it)."""
    _require_topology()
    row = pm.run_workload("dec_tiny",
                          lambda: pm.wl_vllm_decode("1b", tiny=True),
                          verbose=False)
    assert row["bytes_accessed"] > 0
    row = pm.run_workload("mllama_dec_tiny",
                          lambda: pm.wl_mllama_decode(tiny=True),
                          verbose=False)
    assert row["family"] == "mllama" and row["bytes_accessed"] > 0
    # the TP-sharded variant: shard_map'd paged kernel + EngineShardings
    # must partition AND lower for the real XLA:TPU backend
    row = pm.run_workload("tp_dec_tiny",
                          lambda: pm.wl_vllm_decode_tp8(tiny=True),
                          verbose=False)
    assert row["n_devices"] == 2 and row["bytes_accessed"] > 0
