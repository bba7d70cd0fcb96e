"""Per-shape attention micro-bench: XLA fused vs Pallas flash, on chip.

Times every attention geometry the SD2.1 UNet emits (B=2 CFG batch) with
scan-amortized jitted loops (50 chained iterations per measurement, so
host dispatch noise cancels). The output drives the `_XLA_SCORE_BUDGET`
dispatch constant in ``ops.attention``.

  python scripts/perf_attn.py
"""

from __future__ import annotations

import time

import jax
import jax.numpy as jnp

from scalable_hw_agnostic_inference_tpu.ops.attention import dot_product_attention

# (label, B, T, S, H, D) — every UNet attention instance at 512px, B=2
SHAPES = [
    ("L0 self 64x64", 2, 4096, 4096, 5, 64),
    ("L0 cross S=77", 2, 4096, 77, 5, 64),
    ("L1 self 32x32", 2, 1024, 1024, 10, 64),
    ("L1 cross S=77", 2, 1024, 77, 10, 64),
    ("L2 self 16x16", 2, 256, 256, 20, 64),
    ("L2 cross S=77", 2, 256, 77, 20, 64),
    ("mid self 8x8", 2, 64, 64, 20, 64),
    ("mid cross S=77", 2, 64, 77, 20, 64),
]

ITERS = 50


def bench_impl(B, T, S, H, D, impl) -> float:
    import numpy as np

    rng = jax.random.PRNGKey(0)
    q = jax.random.normal(rng, (B, T, H, D), jnp.bfloat16)
    k = jax.random.normal(rng, (B, S, H, D), jnp.bfloat16)
    v = jax.random.normal(rng, (B, S, H, D), jnp.bfloat16)

    @jax.jit
    def loop(q, k, v):
        def body(qc, _):
            o = dot_product_attention(qc, k, v, impl=impl)
            return o + qc * 1e-6, None  # feed forward: serialize iterations

        out, _ = jax.lax.scan(body, q, None, length=ITERS)
        # tiny forced output: np.asarray of it is the sync
        return out[0, 0, 0, :8].astype(jnp.float32)

    np.asarray(loop(q, k, v))
    best = float("inf")
    for _ in range(3):
        t0 = time.perf_counter()
        np.asarray(loop(q, k, v))
        best = min(best, time.perf_counter() - t0)
    return best / ITERS * 1e6  # us per call


def main() -> None:
    from scalable_hw_agnostic_inference_tpu.core.aot import (
        enable_persistent_cache,
    )

    enable_persistent_cache()
    impls = ("xla", "pallas", "jax-flash")
    print(f"{'shape':16s} " + " ".join(f"{i:>10s}" for i in impls) + "  winner")
    for label, B, T, S, H, D in SHAPES:
        times = []
        for impl in impls:
            try:
                times.append(bench_impl(B, T, S, H, D, impl))
            except Exception:
                times.append(float("inf"))
        win = impls[times.index(min(times))]
        print(f"{label:16s} " + " ".join(f"{t:10.1f}" for t in times)
              + f"  {win}  (T*S={T*S})")


if __name__ == "__main__":
    main()
