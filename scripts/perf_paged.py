"""On-chip paged-vs-dense decode attention measurement (VERDICT r2 #4).

Builds a Llama-3.2-1B-geometry decode step at several context windows and
times 50 chained decode calls (async dispatch, one forced sync at the end)
for the dense-gather path vs the Pallas paged kernel, at full and single-
sequence occupancy. "Done" criterion from the verdict: decode cost must
scale with blocks actually used, not the bucket window.

  python scripts/perf_paged.py
"""

from __future__ import annotations

import time

import jax
import jax.numpy as jnp
import numpy as np

from scalable_hw_agnostic_inference_tpu.engine.runner import make_decode
from scalable_hw_agnostic_inference_tpu.models.convert import cast_f32_to_bf16
from scalable_hw_agnostic_inference_tpu.models.llama import (
    LlamaConfig,
    LlamaForCausalLM,
)

BS = 16           # block size (tokens)
B = 8             # slot batch
STEPS = 50


def bench(cfg, params, kv, ctx_blocks, n_active, paged):
    M = ctx_blocks
    fn = make_decode(cfg, BS, M, B, paged=paged)
    rng = np.random.default_rng(0)
    tables = np.zeros((B, M), np.int32)
    pos = np.zeros((B,), np.int32)
    blocks = iter(rng.permutation(np.arange(1, B * M + 1)))
    for b in range(n_active):
        n_tok = M * BS - 1
        nb = -(-n_tok // BS)
        for j in range(nb):
            tables[b, j] = next(blocks)
        pos[b] = n_tok - 1
    args = [params, kv, jnp.zeros((B,), jnp.int32), jnp.asarray(pos),
            jnp.asarray(tables), jnp.asarray(np.arange(B) < n_active),
            jax.random.PRNGKey(0), jnp.int32(0), jnp.ones((B,), jnp.float32),
            jnp.zeros((B,), jnp.int32), jnp.ones((B,), jnp.float32)]
    kv2, nxt, *_ = fn(*args)
    np.asarray(nxt)  # warm + sync
    t0 = time.perf_counter()
    for _ in range(STEPS):
        args[1] = kv2
        kv2, nxt, *_ = fn(*args)
    np.asarray(nxt)  # one forced sync for the chain
    dt = (time.perf_counter() - t0) / STEPS * 1e3
    return dt, kv2


def main() -> None:
    from scalable_hw_agnostic_inference_tpu.core.aot import (
        enable_persistent_cache,
        host_init,
        to_default_device,
    )

    enable_persistent_cache()
    cfg = LlamaConfig(
        vocab_size=128256, dim=2048, n_layers=16, n_heads=32, n_kv_heads=8,
        mlp_dim=8192, max_seq_len=32768, rope_theta=500000.0,
        tie_embeddings=True)
    model = LlamaForCausalLM(cfg, dtype=jnp.bfloat16)
    params = to_default_device(cast_f32_to_bf16(host_init(
        model.init, lambda: jax.random.PRNGKey(0),
        lambda: jnp.zeros((1, 8), jnp.int32))))

    print(f"{'ctx tokens':>10s} {'occ':>4s} {'dense ms':>9s} {'paged ms':>9s}")
    for ctx_tokens in (1024, 4096, 16384):
        M = ctx_tokens // BS
        # +1: block 0 is the reserved null block; full occupancy needs B*M
        # allocatable blocks on top of it
        shape = (B * M + 1, BS, cfg.n_kv_heads, cfg.head_dim)
        for n_active in (B, 1):
            kv = [{"k": jnp.zeros(shape, jnp.bfloat16),
                   "v": jnp.zeros(shape, jnp.bfloat16)}
                  for _ in range(cfg.n_layers)]
            t_dense, kv = bench(cfg, params, kv, M, n_active, paged=False)
            kv = [{"k": jnp.zeros(shape, jnp.bfloat16),
                   "v": jnp.zeros(shape, jnp.bfloat16)}
                  for _ in range(cfg.n_layers)]
            t_paged, kv = bench(cfg, params, kv, M, n_active, paged=True)
            print(f"{ctx_tokens:>10d} {n_active:>4d} {t_dense:>9.2f} "
                  f"{t_paged:>9.2f}")
        del kv


if __name__ == "__main__":
    main()
