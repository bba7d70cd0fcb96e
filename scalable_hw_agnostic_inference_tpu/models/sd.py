"""Stable Diffusion 2.1 pipeline: the flagship serving unit, TPU-first.

Parity target: the reference's SD2.1 path — ``app/compile-sd2.py:13-20``
(AOT export), ``app/run-sd.py``/``run-sd2.py`` (serving, 512x512, 25 steps).
The reference crosses the host boundary every denoise step (diffusers
scheduler loop around a traced UNet). Here the ENTIRE denoise loop is one
jitted ``lax.scan`` — text-cond + uncond batched through the UNet as [2B]
(classifier-free guidance in one forward), scheduler step as pure table math,
no host round-trips until the decoded image. Static (H, W, steps) per
compiled executable, bucketed by ``core.bucketing``.

Components: CLIP text encoder (``models.clip``), UNet (``models.unet``),
VAE (``models.vae``), schedulers (``models.schedulers``).
"""

from __future__ import annotations

import dataclasses
from typing import Any, Callable, Dict, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from .schedulers import EulerDiscrete, ScheduleConfig, get_scheduler
from .unet import UNet2DCondition, UNetConfig
from .vae import AutoencoderKL, VAEConfig


@dataclasses.dataclass(frozen=True)
class SDVariant:
    """Model-family geometry + schedule parameterization."""

    name: str
    unet: UNetConfig
    vae: VAEConfig
    schedule: ScheduleConfig
    default_size: int = 512

    @classmethod
    def sd21_base(cls) -> "SDVariant":
        """stabilityai/stable-diffusion-2-1-base: 512px, epsilon."""
        return cls("sd21-base", UNetConfig.sd21(), VAEConfig(),
                   ScheduleConfig(prediction_type="epsilon"), 512)

    @classmethod
    def sd21(cls) -> "SDVariant":
        """stabilityai/stable-diffusion-2-1: 768px, v-prediction."""
        return cls("sd21", UNetConfig.sd21(), VAEConfig(),
                   ScheduleConfig(prediction_type="v_prediction"), 768)

    @classmethod
    def sd15(cls) -> "SDVariant":
        return cls("sd15", UNetConfig.sd15(), VAEConfig(),
                   ScheduleConfig(prediction_type="epsilon"), 512)

    @classmethod
    def tiny(cls) -> "SDVariant":
        return cls("tiny", UNetConfig.tiny(), VAEConfig.tiny(),
                   ScheduleConfig(prediction_type="epsilon"), 64)


VARIANTS = {
    "sd21-base": SDVariant.sd21_base,
    "sd21": SDVariant.sd21,
    "sd15": SDVariant.sd15,
    "tiny": SDVariant.tiny,
}


class StableDiffusion:
    """Jit-once txt2img. Construct, then call :meth:`txt2img`.

    ``text_encode(ids) -> [B, L, ctx]`` is injected so the same pipeline
    drives the real CLIP encoder or a test stub.
    """

    def __init__(
        self,
        variant: SDVariant,
        unet_params: Dict[str, Any],
        vae_params: Dict[str, Any],
        text_encode: Callable[[jax.Array], jax.Array],
        scheduler: str = "ddim",
        dtype=jnp.bfloat16,
    ):
        self.variant = variant
        self.unet = UNet2DCondition(variant.unet, dtype=dtype)
        self.vae = AutoencoderKL(variant.vae)
        self.unet_params = unet_params
        self.vae_params = vae_params
        self.text_encode = text_encode
        self.scheduler_name = scheduler
        self.scheduler = get_scheduler(scheduler, variant.schedule)
        # spatial down-factor of the VAE (8 for the SD VAE's 4 levels)
        self.vae_scale = 2 ** (len(variant.vae.block_out) - 1)
        self._denoise_cache: Dict[Tuple[int, int, int, int], Callable] = {}

        def _decode_u8(p, z):
            # decode + [-1,1] -> uint8 on device: one small uint8 transfer
            # instead of an fp32 image + host-side clip/scale round-trips
            img = self.vae.apply(p, z, method=AutoencoderKL.decode)
            img = jnp.clip(img * 127.5 + 127.5, 0.0, 255.0)
            return jnp.round(img).astype(jnp.uint8)

        self._decode = jax.jit(_decode_u8)

    # -- jit builders -----------------------------------------------------

    def _build_denoise(self, B: int, h: int, w: int, steps: int) -> Callable:
        """The denoise scan alone (latents out, no decode). Serving goes
        through the fused pipeline; this and ``_decode`` exist so the perf
        harness (``scripts/perf_sd.py``) can time the stages separately."""
        body = self._denoise_body(B, h, w, steps)
        return jax.jit(body)

    def _make_step(self, B: int) -> Callable:
        """THE denoise step (CFG doubling, guidance mix, scheduler update) —
        the body of the fused pipeline's scan, and the unit the offline perf
        model compiles on its own (``perf.model.wl_sd_step``)."""
        sch = self.scheduler
        unet = self.unet
        is_euler = isinstance(sch, EulerDiscrete)

        def one(unet_params, lat, t, a, a2, ctx2, guidance):
            model_in = sch.scale_model_input(lat, a) if is_euler else lat
            pair = jnp.concatenate([model_in, model_in], axis=0)
            tt = jnp.full((2 * B,), t, jnp.int32)
            out = unet.apply(unet_params, pair, tt, ctx2)
            out_u, out_c = jnp.split(out, 2, axis=0)
            out = out_u + guidance * (out_c - out_u)
            return sch.step(lat, out, a, a2)

        return one

    def _init_scale(self, steps: int) -> float:
        sch = self.scheduler
        if isinstance(sch, EulerDiscrete):
            return sch.init_sigma_for(steps)
        return sch.init_noise_sigma

    def _denoise_body(self, B: int, h: int, w: int, steps: int) -> Callable:
        sch = self.scheduler
        latent_ch = self.variant.unet.in_channels
        tables = sch.tables(steps)
        init_scale = self._init_scale(steps)
        one = self._make_step(B)

        def denoise(unet_params, ctx2, rng, guidance):
            latents = jax.random.normal(
                rng, (B, h, w, latent_ch), jnp.float32
            ) * init_scale

            def body(lat, xs):
                t, a, a2 = xs
                return one(unet_params, lat, t, a, a2, ctx2, guidance), None

            lat, _ = jax.lax.scan(body, latents, tables)
            return lat

        return denoise

    def _decode_body(self, vae_params, lat: jax.Array) -> jax.Array:
        """VAE decode + uint8 quantize inside a pipeline trace.

        On TPU, batches 2-4 decode per-image via ``lax.map``: XLA:TPU's
        fused batch-2/4 VAE decode is HBM-pathological — the offline cost
        model measured 115 GB accessed at batch 4 fused vs 35 GB as four
        single-image decodes (PERF_MODEL.md, sd_vae_b4 vs sd_vae_b4_split;
        batch 8 fuses fine at 30 GB). The split is platform-gated like the
        attention dispatch (only measured on XLA:TPU); row independence is
        exact either way (decode is per-image), covered by the
        composition-invariance test.
        """
        from ..ops.attention import on_tpu_platform

        def dec(z):
            img = self.vae.apply(vae_params, z, method=AutoencoderKL.decode)
            img = jnp.clip(img * 127.5 + 127.5, 0.0, 255.0)
            return jnp.round(img).astype(jnp.uint8)

        if 2 <= lat.shape[0] <= 4 and on_tpu_platform():
            return jax.lax.map(lambda z: dec(z[None])[0], lat)
        return dec(lat)

    def _build_pipeline(self, B: int, h: int, w: int, steps: int) -> Callable:
        """Denoise scan + VAE decode + uint8 quantize as ONE executable.

        One device call and one (uint8) transfer per image: host round-trips
        between denoise and decode are pure latency.
        """
        denoise = self._denoise_body(B, h, w, steps)

        def full(unet_params, vae_params, ctx2, rng, guidance):
            lat = denoise(unet_params, ctx2, rng, guidance)
            return self._decode_body(vae_params, lat)

        return jax.jit(full)

    def _denoise_for(self, B: int, h: int, w: int, steps: int) -> Callable:
        key = (B, h, w, steps)
        if key not in self._denoise_cache:
            self._denoise_cache[key] = self._build_pipeline(B, h, w, steps)
        return self._denoise_cache[key]

    def _build_pipeline_from_latents(self, B: int, h: int, w: int,
                                     steps: int) -> Callable:
        """The fused pipeline with LATENTS AS AN ARGUMENT.

        The serving coalescer batches concurrent requests into one denoise
        call; each request keeps its own seed by materializing its [1,h,w,C]
        init noise host-side (identical math to the in-graph init: same key,
        same shape) and stacking — so a request's image is a function of its
        own (seed, prompt), independent of which batch it landed in.
        """
        sch = self.scheduler
        tables = sch.tables(steps)
        one = self._make_step(B)

        def full(unet_params, vae_params, ctx2, latents, guidance):
            def body(lat, xs):
                t, a, a2 = xs
                return one(unet_params, lat, t, a, a2, ctx2, guidance), None

            lat, _ = jax.lax.scan(body, latents, tables)
            return self._decode_body(vae_params, lat)

        return jax.jit(full)

    def init_latents(self, seed: int, h: int, w: int, steps: int) -> jax.Array:
        """One request's [1,h,w,C] init noise — the exact tensor the
        in-graph path draws from ``PRNGKey(seed)``."""
        lat = jax.random.normal(
            jax.random.PRNGKey(seed),
            (1, h, w, self.variant.unet.in_channels), jnp.float32)
        return lat * self._init_scale(steps)

    def txt2img_batch(
        self,
        prompt_ids: jax.Array,    # [B, L]
        uncond_ids: jax.Array,    # [B, L]
        latents: jax.Array,       # [B, h, w, C] (stacked init_latents)
        *,
        height: int,
        width: int,
        steps: int = 25,
        guidance_scale: float = 7.5,
    ) -> np.ndarray:
        """Batched :meth:`txt2img` over pre-drawn latents (the coalescer
        path). Returns uint8 [B, H, W, 3]."""
        f = self.vae_scale
        B = prompt_ids.shape[0]
        key = ("batch", B, height // f, width // f, steps)
        if key not in self._denoise_cache:
            self._denoise_cache[key] = self._build_pipeline_from_latents(
                B, height // f, width // f, steps)
        ctx2 = self.text_encode(jnp.concatenate([uncond_ids, prompt_ids], axis=0))
        img = self._denoise_cache[key](
            self.unet_params, self.vae_params, ctx2, latents,
            jnp.float32(guidance_scale))
        return np.asarray(img)

    # -- public API -------------------------------------------------------

    def txt2img(
        self,
        prompt_ids: jax.Array,    # [B, L] tokenized prompt
        uncond_ids: jax.Array,    # [B, L] tokenized "" (negative prompt)
        *,
        rng: jax.Array,
        height: int,
        width: int,
        steps: int = 25,
        guidance_scale: float = 7.5,
    ) -> np.ndarray:
        """Returns uint8 images [B, H, W, 3]."""
        f = self.vae_scale
        if height % f or width % f:
            raise ValueError(f"height/width must be multiples of {f}")
        B = prompt_ids.shape[0]
        # uncond first, cond second — split order in the denoise body
        ctx2 = self.text_encode(jnp.concatenate([uncond_ids, prompt_ids], axis=0))
        img = self._denoise_for(B, height // f, width // f, steps)(
            self.unet_params, self.vae_params, ctx2, rng,
            jnp.float32(guidance_scale)
        )
        return np.asarray(img)

    def warm(self, B: int, height: int, width: int, steps: int, seq_len: int) -> None:
        """Compile-warm one (B, H, W, steps) shape before readiness."""
        ids = jnp.zeros((B, seq_len), jnp.int32)
        self.txt2img(ids, ids, rng=jax.random.PRNGKey(0), height=height,
                     width=width, steps=steps, guidance_scale=7.5)


# ---------------------------------------------------------------------------
# checkpoint loading (diffusers directory layout, no diffusers dependency)
# ---------------------------------------------------------------------------

def resolve_checkpoint_dir(model_id: str, token: str = "") -> str:
    """Local dir as-is; otherwise pull the needed subfolders from the hub.

    FLUX repos carry the transformer twice (root ``flux1-*.safetensors`` and
    the diffusers ``transformer/`` shards) — download only the layout the
    repo actually has, preferring the single file, so a plain diffusers-only
    snapshot still serves (VERDICT r2 #7) without ever pulling both copies.
    """
    import os

    if os.path.isdir(model_id):
        return model_id
    from huggingface_hub import snapshot_download

    patterns = ["unet/*", "vae/*", "text_encoder/*", "tokenizer/*",
                "text_encoder_2/*", "tokenizer_2/*",  # flux T5/CLIP pair
                "scheduler/*", "*.json"]
    try:
        from huggingface_hub import list_repo_files

        files = list_repo_files(model_id, token=token or None)
        if any(f.startswith("flux1-") and f.endswith(".safetensors")
               for f in files):
            patterns.append("flux1-*.safetensors")
        elif any(f.startswith("transformer/") for f in files):
            patterns.append("transformer/*")
    except Exception:
        # listing unavailable (offline mirror): ask for both layouts; the
        # hub only serves what exists
        patterns += ["flux1-*.safetensors", "transformer/*"]
    return snapshot_download(model_id, token=token or None,
                             allow_patterns=patterns)


def load_torch_state(component_dir: str) -> Dict[str, Any]:
    """State dict of one pipeline component (safetensors preferred)."""
    import os

    st = os.path.join(component_dir, "diffusion_pytorch_model.safetensors")
    if os.path.exists(st):
        from safetensors.torch import load_file

        return load_file(st)
    bin_path = os.path.join(component_dir, "diffusion_pytorch_model.bin")
    if os.path.exists(bin_path):
        import torch

        return torch.load(bin_path, map_location="cpu", weights_only=True)
    raise FileNotFoundError(f"no weights found under {component_dir}")


def variant_from_checkpoint(root: str) -> SDVariant:
    """Build an :class:`SDVariant` from a checkpoint's component configs."""
    import json
    import os

    with open(os.path.join(root, "unet", "config.json")) as f:
        unet_cfg = json.load(f)
    with open(os.path.join(root, "vae", "config.json")) as f:
        vae_cfg = json.load(f)
    sched_path = os.path.join(root, "scheduler", "scheduler_config.json")
    sched: Dict[str, Any] = {}
    if os.path.exists(sched_path):
        with open(sched_path) as f:
            sched = json.load(f)
    schedule = ScheduleConfig(
        num_train_timesteps=sched.get("num_train_timesteps", 1000),
        beta_start=sched.get("beta_start", 0.00085),
        beta_end=sched.get("beta_end", 0.012),
        beta_schedule=sched.get("beta_schedule", "scaled_linear"),
        prediction_type=sched.get("prediction_type", "epsilon"),
        steps_offset=sched.get("steps_offset", 1),
    )
    return SDVariant(
        name=os.path.basename(root.rstrip("/")),
        unet=UNetConfig.from_hf(unet_cfg),
        vae=VAEConfig.from_hf(vae_cfg),
        schedule=schedule,
        default_size=unet_cfg.get("sample_size", 64) * 8,
    )


def to_png_base64(img: np.ndarray) -> str:
    """uint8 [H, W, 3] -> base64 PNG string (the reference's wire format,
    ``app/run-sd.py:177-181``)."""
    import base64
    import io

    from PIL import Image

    buf = io.BytesIO()
    Image.fromarray(img).save(buf, format="PNG")
    return base64.b64encode(buf.getvalue()).decode()
