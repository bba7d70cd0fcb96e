"""The gated short convolution alone, at LFM2-24B-A2B's widths (2048
channels, 3 taps), plain XLA against its own bytes' time, and the attention
kernels at its 64-wide heads: ``chiprun -- python scripts/shortconv_bench.py``
(``--dry-run``: tiny, on the CPU, kernels interpreted; its times mean
nothing). The twin of ``scripts/ssm_bench.py``.

The mixer at 128 rows of one token (decode: the rows' tails read from and
written to a 129-slot arena) and at 4 rows of 512 tokens (prefill from a
zero tail), eight calls chained in one program. Timed twice: whole (the in
projection, gate, convolve, gate, the out projection, the slot write), and
with the passes between the two matmuls taken out (``out = C * u``, no tail).
The difference is what the gate-convolve-gate passes and the slot traffic
cost; their bytes (the in projection's ``[rows, 3 D]`` read, ``[rows, D]``
written, the tails read and written) over 819 GB/s is the least they could.
ISSUE 50's rule: a Pallas kernel is written only if that ratio passes 1.25.

Then ``ops.kernel_check.engine_cases`` at 32 query heads over 8 key/value
heads: flash at ``D = 64`` (as declared) and at the 128 lanes the engine
serves them on (``LlamaConfig.head_lanes``), the paged kernel at 128 (Mosaic
refuses it at 64: PERF.md section 6, PR 50), each against its oracle. Writes
``chiprun_out/shortconv_bench.json``.
"""

import json
import os
import sys
import time
import types

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, ROOT)

import jax                      # noqa: E402
import jax.numpy as jnp         # noqa: E402

from scalable_hw_agnostic_inference_tpu.ops import kernel_check, shortconv  # noqa: E402

HBM_BYTES_PER_S = 819e9   # benchmark/peaks.json
STEPS = 8


def timed(f, args, n):
    for _ in range(2):
        jax.block_until_ready(f(*args))
    t0 = time.perf_counter()
    for _ in range(n):
        out = f(*args)
    jax.block_until_ready(out)
    return (time.perf_counter() - t0) / n / STEPS


def mixer(dry):
    D, K = (128, 3) if dry else (2048, 3)
    slots = 4 if dry else 128
    n = 2 if dry else 50
    cfg = types.SimpleNamespace(dim=D, conv_taps=K)
    k0, k1, k2, k3 = jax.random.split(jax.random.PRNGKey(0), 4)
    at = {"in": {"kernel": (0.02 * jax.random.normal(k0, (D, 3 * D))
                            ).astype(jnp.bfloat16)},
          "conv": jax.random.uniform(k1, (K, D), jnp.float32, -0.577, 0.577
                                     ).astype(jnp.bfloat16),
          "o": {"kernel": (0.02 * jax.random.normal(k2, (D, D))
                           ).astype(jnp.bfloat16)}}
    out = []
    for name, rows, T in (("decode", slots, 1),
                          ("prefill", 2 if dry else 4, 32 if dry else 512)):
        h = jax.random.normal(k3, (rows, T, D), jnp.float32
                              ).astype(jnp.bfloat16)
        arena = {"t": jnp.zeros((slots + 1, K - 1, D), jnp.bfloat16)}
        ids = jnp.arange(rows, dtype=jnp.int32)
        n_valid = jnp.full((rows,), T, jnp.int32)

        def residual(h, o):
            # a step needs the one before; normed, as a layer's stream is
            x = h.astype(jnp.float32) + o @ at["o"]["kernel"]
            return (x * jax.lax.rsqrt(jnp.mean(x * x, -1, keepdims=True))
                    ).astype(h.dtype)

        def whole(h, arena):
            for _ in range(STEPS):
                if T == 1:
                    o, arena = shortconv.decode(at, h, arena, ids, cfg,
                                                kernel=False)
                else:
                    o, arena = shortconv.prefill(at, h, arena, ids, n_valid,
                                                 cfg, carry=False,
                                                 kernel=False)
                h = residual(h, o)
            return h, arena

        def matmuls(h, arena):
            for _ in range(STEPS):
                _, c, u = jnp.split(h @ at["in"]["kernel"], 3, axis=-1)
                h = residual(h, c * u)
            return h, arena

        got, _ = jax.jit(whole)(h, arena)
        t_whole = timed(jax.jit(whole), (h, arena), n)
        t_mm = timed(jax.jit(matmuls), (h, arena), n)
        tokens = rows * T
        least = (tokens * 4 * D + 2 * rows * (K - 1) * D) * 2 / HBM_BYTES_PER_S
        rec = {"case": f"shortconv-{name}-D{D}-K{K}-b{rows}-T{T}",
               "whole_us": t_whole * 1e6, "matmuls_us": t_mm * 1e6,
               "passes_us": (t_whole - t_mm) * 1e6,
               "bytes_us": least * 1e6,
               "passes_over_bytes": (t_whole - t_mm) / least,
               "ok": bool(jnp.isfinite(got.astype(jnp.float32)).all())}
        print(json.dumps(rec), flush=True)
        out.append(rec)
    return out


def attention(dry):
    out = []
    for D in (64, 128):
        kw = dict(buckets=(32,), max_model_len=64, max_num_seqs=4) if dry \
            else dict(buckets=(256, 512), max_model_len=1600,
                      max_num_seqs=128)
        H, Hkv = (4, 2) if dry else (32, 8)
        for case in kernel_check.engine_cases(H, Hkv, D, **kw):
            if "int8" in case.name or (
                    D == 64 and not dry and "paged" in case.name):
                continue       # no int8 pool here; Mosaic refuses paged at 64
            rec = {"case": f"{case.name}-D{D}", "tol": case.tol,
                   "max_abs_err": case.max_abs_err(interpret=dry)}
            rec["ok"] = rec["max_abs_err"] <= case.tol
            print(json.dumps(rec), flush=True)
            out.append(rec)
    return out


def main():
    dry = "--dry-run" in sys.argv
    if not dry and jax.default_backend() != "tpu":
        sys.exit("shortconv_bench needs the chip (or --dry-run)")
    out = mixer(dry) + attention(dry)
    if not dry:
        dev = jax.devices()[0]
        out.append({"device": dev.device_kind, "platform": dev.platform})
        os.makedirs(os.path.join(ROOT, "chiprun_out"), exist_ok=True)
        with open(os.path.join(ROOT, "chiprun_out", "shortconv_bench.json"),
                  "w") as fh:
            json.dump(out, fh, indent=1)
    sys.exit(0 if all(r.get("ok", True) for r in out) else 1)


if __name__ == "__main__":
    main()
