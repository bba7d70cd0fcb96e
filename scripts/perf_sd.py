"""SD2.1 on-chip perf breakdown harness (VERDICT r2 next-round item 1).

Times the pipeline's components separately on the real chip so the perf work
attacks measured costs, not guesses:

  python scripts/perf_sd.py            # component breakdown
  python scripts/perf_sd.py --trace    # also dump a jax.profiler trace

Reports: single UNet CFG forward (B=2), 25-step denoise scan, VAE decode
(current dtype), and the end-to-end txt2img, each as ms and as a share of
the 25-step total.
"""

from __future__ import annotations

import sys
import time

import jax
import jax.numpy as jnp

from scalable_hw_agnostic_inference_tpu.models import sd as sd_mod
from scalable_hw_agnostic_inference_tpu.models.convert import cast_f32_to_bf16


def _sync(out):
    # a host transfer of (a leaf of) the result is the sync
    import numpy as np

    leaf = jax.tree.leaves(out)[0]
    np.asarray(leaf).ravel()[:1]
    return out


def timed(fn, *args, runs=5, warm=1):
    for _ in range(warm):
        out = _sync(fn(*args))
    t0 = time.perf_counter()
    for _ in range(runs):
        out = _sync(fn(*args))
    return (time.perf_counter() - t0) / runs, out


def main() -> None:
    from scalable_hw_agnostic_inference_tpu.core.aot import (
        enable_persistent_cache,
        host_init,
        to_default_device,
    )

    enable_persistent_cache()

    size, steps, seq = 512, 25, 77
    variant = sd_mod.SDVariant.sd21_base()
    unet = sd_mod.UNet2DCondition(variant.unet)
    f = 2 ** (len(variant.vae.block_out) - 1)
    lat = size // f
    D = variant.unet.cross_attention_dim

    unet_params = host_init(
        unet.init, lambda: jax.random.PRNGKey(0),
        lambda: jnp.zeros((1, lat, lat, variant.unet.in_channels)),
        lambda: jnp.zeros((1,), jnp.int32),
        lambda: jnp.zeros((1, seq, D)))
    unet_params = to_default_device(cast_f32_to_bf16(unet_params))
    vae = sd_mod.AutoencoderKL(variant.vae)
    vae_params = to_default_device(host_init(
        vae.init, lambda: jax.random.PRNGKey(1),
        lambda: jnp.zeros((1, lat, lat, variant.vae.latent_channels))))
    rng = jax.random.PRNGKey(0)

    def text_encode(ids):
        return jax.nn.one_hot(ids % D, D, dtype=jnp.bfloat16)

    pipe = sd_mod.StableDiffusion(variant, unet_params, vae_params, text_encode)
    ids = jnp.zeros((1, seq), jnp.int32)

    # single UNet CFG forward (the denoise body without the scan)
    fwd = jax.jit(lambda p, x, t, c: unet.apply(p, x, t, c))
    x2 = jnp.zeros((2, lat, lat, 4), jnp.float32)
    t2 = jnp.zeros((2,), jnp.int32)
    c2 = text_encode(jnp.zeros((2, seq), jnp.int32))
    t_fwd, _ = timed(fwd, unet_params, x2, t2, c2)

    # the full jitted denoise scan (latent out, no decode)
    den = pipe._build_denoise(1, lat, lat, steps)
    t_den, latents = timed(den, unet_params, c2, rng, jnp.float32(7.5))

    # VAE decode as shipped
    t_vae, _ = timed(pipe._decode, vae_params, latents)

    # end to end
    def e2e():
        return pipe.txt2img(ids, ids, rng=rng, height=size, width=size, steps=steps)
    t_e2e, _ = timed(e2e, runs=3)

    total = t_den + t_vae
    print(f"unet fwd (B=2)     : {t_fwd*1e3:8.1f} ms   x{steps} = {t_fwd*steps*1e3:8.1f} ms")
    print(f"denoise scan ({steps}) : {t_den*1e3:8.1f} ms   ({t_den/total*100:4.1f}% of scan+vae)")
    print(f"  scan overhead    : {(t_den - t_fwd*steps)*1e3:8.1f} ms (scan - steps*fwd)")
    print(f"vae decode         : {t_vae*1e3:8.1f} ms   ({t_vae/total*100:4.1f}% of scan+vae)")
    print(f"txt2img e2e        : {t_e2e*1e3:8.1f} ms   -> {1.0/t_e2e:.4f} img/s")

    if "--trace" in sys.argv:
        with jax.profiler.trace("/tmp/sd_trace"):
            pipe.txt2img(ids, ids, rng=rng, height=size, width=size, steps=steps)
        print("trace written to /tmp/sd_trace")


if __name__ == "__main__":
    main()
