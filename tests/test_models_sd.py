"""SD2.1 stack tests: schedulers, UNet, VAE, pipeline, converter structure.

Numerical scheduler identities are checked analytically (no diffusers in the
image); converters are checked for exact tree-structure/shape agreement with
``model.init`` via synthetic torch state dicts in the published layout.
"""

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from scalable_hw_agnostic_inference_tpu.models import sd as sd_mod
from scalable_hw_agnostic_inference_tpu.models import unet as unet_mod
from scalable_hw_agnostic_inference_tpu.models import vae as vae_mod
from scalable_hw_agnostic_inference_tpu.models.schedulers import (
    DDIM,
    EulerDiscrete,
    ScheduleConfig,
    inference_timesteps,
    pred_x0_and_eps,
)


# ---------------------------------------------------------------------------
# schedulers
# ---------------------------------------------------------------------------

def test_inference_timesteps_leading():
    cfg = ScheduleConfig()
    ts = inference_timesteps(cfg, 25)
    assert ts.shape == (25,)
    assert ts[0] > ts[-1] >= 0
    assert ts.max() < cfg.num_train_timesteps
    # leading spacing with offset 1: last timestep is steps_offset
    assert ts[-1] == cfg.steps_offset


def test_ddim_step_recovers_x0_at_final_step():
    """With perfect eps and acp_prev=1, DDIM returns exactly x0."""
    cfg = ScheduleConfig(prediction_type="epsilon")
    sch = DDIM(cfg)
    rng = np.random.default_rng(0)
    x0 = jnp.asarray(rng.standard_normal((2, 4, 4, 3)), jnp.float32)
    eps = jnp.asarray(rng.standard_normal((2, 4, 4, 3)), jnp.float32)
    t = jnp.array([500])
    xt = sch.add_noise(x0, eps, t)
    out = sch.step(xt, eps, jnp.float32(sch.alphas_cumprod[500]), jnp.float32(1.0))
    np.testing.assert_allclose(np.asarray(out), np.asarray(x0), atol=1e-4)


def test_v_prediction_consistency():
    """v-parameterization: recovered (x0, eps) must satisfy the forward eq."""
    acp = jnp.float32(0.37)
    rng = np.random.default_rng(1)
    x0 = jnp.asarray(rng.standard_normal((8,)), jnp.float32)
    eps = jnp.asarray(rng.standard_normal((8,)), jnp.float32)
    sample = jnp.sqrt(acp) * x0 + jnp.sqrt(1 - acp) * eps
    v = jnp.sqrt(acp) * eps - jnp.sqrt(1 - acp) * x0
    rx0, reps = pred_x0_and_eps(sample, v, acp, "v_prediction")
    np.testing.assert_allclose(np.asarray(rx0), np.asarray(x0), atol=1e-5)
    np.testing.assert_allclose(np.asarray(reps), np.asarray(eps), atol=1e-5)


def test_euler_step_exact_denoise():
    """Perfect eps and sigma_next=0 lands exactly on x0 (unscaled space)."""
    sch = EulerDiscrete(ScheduleConfig(prediction_type="epsilon"))
    rng = np.random.default_rng(2)
    x0 = jnp.asarray(rng.standard_normal((4,)), jnp.float32)
    eps = jnp.asarray(rng.standard_normal((4,)), jnp.float32)
    sigma = jnp.float32(3.0)
    xt = x0 + sigma * eps
    out = sch.step(xt, eps, sigma, jnp.float32(0.0))
    np.testing.assert_allclose(np.asarray(out), np.asarray(x0), atol=1e-5)
    assert sch.init_noise_sigma > 10  # SD ladder tops out >> 1


# ---------------------------------------------------------------------------
# UNet
# ---------------------------------------------------------------------------

@pytest.fixture(scope="module")
def tiny_unet():
    cfg = unet_mod.UNetConfig.tiny()
    model = unet_mod.UNet2DCondition(cfg, dtype=jnp.float32)
    params = model.init(
        jax.random.PRNGKey(0),
        jnp.zeros((1, 8, 8, cfg.in_channels)),
        jnp.zeros((1,), jnp.int32),
        jnp.zeros((1, 8, cfg.cross_attention_dim)),
    )
    return cfg, model, params


@pytest.mark.slow  # tier-1 budget: see scripts/check_tier1_budget.py
def test_unet_forward_shape_and_determinism(tiny_unet):
    cfg, model, params = tiny_unet
    x = jax.random.normal(jax.random.PRNGKey(1), (2, 8, 8, cfg.in_channels))
    t = jnp.array([10, 500], jnp.int32)
    ctx = jax.random.normal(jax.random.PRNGKey(2), (2, 8, cfg.cross_attention_dim))
    out = model.apply(params, x, t, ctx)
    assert out.shape == (2, 8, 8, cfg.out_channels)
    assert out.dtype == jnp.float32
    out2 = model.apply(params, x, t, ctx)
    np.testing.assert_array_equal(np.asarray(out), np.asarray(out2))
    # conditioning actually conditions
    out3 = model.apply(params, x, t, ctx + 1.0)
    assert np.abs(np.asarray(out) - np.asarray(out3)).max() > 1e-6


def test_timestep_embedding_matches_reference_formula():
    emb = unet_mod.timestep_embedding(jnp.array([0.0, 7.0]), 8)
    assert emb.shape == (2, 8)
    # t=0: sin part zero, cos part one; flip_sin_to_cos puts cos first
    np.testing.assert_allclose(np.asarray(emb[0]), [1, 1, 1, 1, 0, 0, 0, 0], atol=1e-6)


def _inverse_linear(p):
    import torch

    out = {"weight": torch.tensor(np.asarray(p["kernel"]).T)}
    if "bias" in p:
        out["bias"] = torch.tensor(np.asarray(p["bias"]))
    return out


def _inverse_conv(p):
    import torch

    return {
        "weight": torch.tensor(np.asarray(p["kernel"]).transpose(3, 2, 0, 1)),
        "bias": torch.tensor(np.asarray(p["bias"])),
    }


def _inverse_norm(p):
    import torch

    return {"weight": torch.tensor(np.asarray(p["scale"])),
            "bias": torch.tensor(np.asarray(p["bias"]))}


def _torch_sd_from_unet_params(params, cfg) -> dict:
    """Synthesize a diffusers-layout state dict matching our tiny tree."""
    sd = {}

    def put(prefix, d):
        for k, v in d.items():
            sd[f"{prefix}.{k}"] = v

    p = params["params"]

    def resnet(tp, fp):
        put(f"{tp}.norm1", _inverse_norm(fp["norm1"]))
        put(f"{tp}.conv1", _inverse_conv(fp["conv1"]))
        put(f"{tp}.time_emb_proj", _inverse_linear(fp["time_emb"]))
        put(f"{tp}.norm2", _inverse_norm(fp["norm2"]))
        put(f"{tp}.conv2", _inverse_conv(fp["conv2"]))
        if "shortcut" in fp:
            put(f"{tp}.conv_shortcut", _inverse_conv(fp["shortcut"]))

    def xformer(tp, fp):
        put(f"{tp}.norm", _inverse_norm(fp["norm"]))
        put(f"{tp}.proj_in", _inverse_linear(fp["proj_in"]))
        put(f"{tp}.proj_out", _inverse_linear(fp["proj_out"]))
        for i in range(cfg.transformer_layers):
            b, fb = f"{tp}.transformer_blocks.{i}", fp[f"block_{i}"]
            for nm in ("norm1", "norm2", "norm3"):
                put(f"{b}.{nm}", _inverse_norm(fb[nm]))
            for attn in ("attn1", "attn2"):
                put(f"{b}.{attn}.to_q", _inverse_linear(fb[attn]["q"]))
                put(f"{b}.{attn}.to_k", _inverse_linear(fb[attn]["k"]))
                put(f"{b}.{attn}.to_v", _inverse_linear(fb[attn]["v"]))
                put(f"{b}.{attn}.to_out.0", _inverse_linear(fb[attn]["o"]))
            put(f"{b}.ff.net.0.proj", _inverse_linear(fb["ff_in"]))
            put(f"{b}.ff.net.2", _inverse_linear(fb["ff_out"]))

    put("time_embedding.linear_1", _inverse_linear(p["time_embed_1"]))
    put("time_embedding.linear_2", _inverse_linear(p["time_embed_2"]))
    put("conv_in", _inverse_conv(p["conv_in"]))
    put("conv_norm_out", _inverse_norm(p["norm_out"]))
    put("conv_out", _inverse_conv(p["conv_out"]))
    resnet("mid_block.resnets.0", p["mid_res_0"])
    resnet("mid_block.resnets.1", p["mid_res_1"])
    xformer("mid_block.attentions.0", p["mid_attn"])
    n = len(cfg.block_out)
    for i in range(n):
        for j in range(cfg.layers_per_block):
            resnet(f"down_blocks.{i}.resnets.{j}", p[f"down_{i}_res_{j}"])
            if cfg.cross_attn[i]:
                xformer(f"down_blocks.{i}.attentions.{j}", p[f"down_{i}_attn_{j}"])
        if i < n - 1:
            put(f"down_blocks.{i}.downsamplers.0.conv",
                _inverse_conv(p[f"down_{i}_conv"]))
    for i in range(n):
        level = n - 1 - i
        for j in range(cfg.layers_per_block + 1):
            resnet(f"up_blocks.{i}.resnets.{j}", p[f"up_{i}_res_{j}"])
            if cfg.cross_attn[level]:
                xformer(f"up_blocks.{i}.attentions.{j}", p[f"up_{i}_attn_{j}"])
        if i < n - 1:
            put(f"up_blocks.{i}.upsamplers.0.conv", _inverse_conv(p[f"up_{i}_conv"]))
    return sd


@pytest.mark.slow  # tier-1 budget: see scripts/check_tier1_budget.py
def test_unet_converter_roundtrip(tiny_unet):
    """converter(inverse(params)) == params — transposes, naming, and tree
    structure all line up with the published layout."""
    cfg, model, params = tiny_unet
    tsd = _torch_sd_from_unet_params(params, cfg)
    conv = unet_mod.params_from_torch(tsd, cfg)
    jax.tree.map(
        lambda a, b: np.testing.assert_allclose(
            np.asarray(a), np.asarray(b), atol=1e-6),
        params, conv,
    )


# ---------------------------------------------------------------------------
# VAE
# ---------------------------------------------------------------------------

def test_vae_decode_encode_shapes():
    cfg = vae_mod.VAEConfig.tiny()
    model = vae_mod.AutoencoderKL(cfg)
    z = jnp.zeros((1, 8, 8, cfg.latent_channels))
    params = model.init(jax.random.PRNGKey(0), z)
    img = model.apply(params, z, method=vae_mod.AutoencoderKL.decode)
    scale = 2 ** (len(cfg.block_out) - 1)
    assert img.shape == (1, 8 * scale, 8 * scale, 3)
    # encoder params are a separate traced path (decode-only serving pods
    # never materialize them)
    enc_params = model.init(
        jax.random.PRNGKey(0), img, method=vae_mod.AutoencoderKL.encode
    )
    mean, logvar = model.apply(enc_params, img, method=vae_mod.AutoencoderKL.encode)
    assert mean.shape == (1, 8, 8, cfg.latent_channels)
    assert logvar.shape == mean.shape


# ---------------------------------------------------------------------------
# pipeline
# ---------------------------------------------------------------------------

@pytest.mark.slow  # tier-1 budget: see scripts/check_tier1_budget.py
def test_txt2img_end_to_end_tiny():
    variant = sd_mod.SDVariant.tiny()
    unet = sd_mod.UNet2DCondition(variant.unet, dtype=jnp.float32)
    up = unet.init(
        jax.random.PRNGKey(0),
        jnp.zeros((1, 8, 8, 4)), jnp.zeros((1,), jnp.int32),
        jnp.zeros((1, 8, variant.unet.cross_attention_dim)),
    )
    vae = sd_mod.AutoencoderKL(variant.vae)
    vp = vae.init(jax.random.PRNGKey(1), jnp.zeros((1, 8, 8, 4)))

    D = variant.unet.cross_attention_dim

    def text_encode(ids):  # stub conditioning: embed token ids directly
        return jax.nn.one_hot(ids % D, D)

    pipe = sd_mod.StableDiffusion(variant, up, vp, text_encode)
    assert pipe.vae_scale == 2
    ids = jnp.array([[3, 5, 7, 9]])
    un = jnp.zeros((1, 4), jnp.int32)
    img = pipe.txt2img(ids, un, rng=jax.random.PRNGKey(0), height=16, width=16,
                       steps=3, guidance_scale=5.0)
    assert img.shape == (1, 16, 16, 3)
    assert img.dtype == np.uint8
    # deterministic given (seed, prompt)
    img2 = pipe.txt2img(ids, un, rng=jax.random.PRNGKey(0), height=16, width=16,
                        steps=3, guidance_scale=5.0)
    np.testing.assert_array_equal(img, img2)
    # prompt changes the image (guidance path is live)
    img3 = pipe.txt2img(ids + 1, un, rng=jax.random.PRNGKey(0), height=16,
                        width=16, steps=3, guidance_scale=5.0)
    assert np.abs(img.astype(int) - img3.astype(int)).max() > 0


def test_png_base64_roundtrip():
    import base64
    import io

    from PIL import Image

    img = (np.random.default_rng(0).random((8, 8, 3)) * 255).astype(np.uint8)
    b64 = sd_mod.to_png_base64(img)
    back = np.asarray(Image.open(io.BytesIO(base64.b64decode(b64))))
    np.testing.assert_array_equal(img, back)


def test_variant_registry():
    assert set(sd_mod.VARIANTS) == {"sd21-base", "sd21", "sd15", "tiny"}
    v = sd_mod.SDVariant.sd21_base()
    assert v.unet.cross_attention_dim == 1024
    assert v.schedule.prediction_type == "epsilon"
    assert sd_mod.SDVariant.sd21().schedule.prediction_type == "v_prediction"


@pytest.mark.slow  # tier-1 budget: see scripts/check_tier1_budget.py
def test_decode_body_split_path_matches_fused():
    """On the TPU target, batches 2-4 VAE-decode per image via lax.map
    (XLA:TPU's fused batch-2/4 decode is HBM-pathological — PERF_MODEL.md);
    the split path must be BIT-EXACT vs decoding each image standalone
    (identical per-image graphs), and match the fused batch to within a few
    uint8 LSBs (fusion order changes float associativity)."""
    import os

    variant = sd_mod.SDVariant.tiny()
    pipe = sd_mod.StableDiffusion(variant, None, None, None)
    vae_params = pipe.vae.init(
        jax.random.PRNGKey(0), jnp.zeros((1, 4, 4, variant.vae.latent_channels)))
    z = jax.random.normal(jax.random.PRNGKey(1),
                          (3, 4, 4, variant.vae.latent_channels), jnp.float32)
    fused = np.asarray(pipe._decode_body(vae_params, z))   # cpu: fused
    old = os.environ.get("SHAI_PLATFORM_OVERRIDE")
    os.environ["SHAI_PLATFORM_OVERRIDE"] = "tpu"           # forces the map path
    try:
        split = np.asarray(pipe._decode_body(vae_params, z))
    finally:
        if old is None:
            os.environ.pop("SHAI_PLATFORM_OVERRIDE", None)
        else:
            os.environ["SHAI_PLATFORM_OVERRIDE"] = old
    per_image = np.stack([
        np.asarray(pipe._decode(vae_params, z[i:i + 1]))[0]
        for i in range(z.shape[0])])
    np.testing.assert_array_equal(split, per_image)
    diff = np.abs(fused.astype(np.int16) - split.astype(np.int16))
    # vs the fused batch: a few LSBs of reassociation drift, nothing
    # structural (tiny random weights amplify it vs real checkpoints)
    assert diff.max() <= 3, f"max pixel diff {diff.max()}"
