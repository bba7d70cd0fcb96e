"""The routed experts' product alone, both forms, at the benchmark cells'
shapes: ``chiprun -- python scripts/moe_bench.py`` (``--dry-run``: tiny, on
the CPU, kernel interpreted; its times mean nothing).

Per shape: rows, top-k and 128 experts of the published widths, an
assignment crafted to touch about as many experts as the cell's counters
read (``experts_touched_mean.moe``, ``expert_load_max_over_mean.moe``), and
three expert layers chained in one program so that the device time of a
layer is the program's over three. Timed: the grouped form (sort, gather,
three ``ragged_dot``, scatter), the streamed kernel at each inner tile, and
the whole ``expert_layer`` (router and shared expert with it) under each
form. ``share`` is the touched experts' bytes at the chip's HBM peak over
the time. Writes ``chiprun_out/moe_bench.json``.
"""

import dataclasses
import json
import os
import sys
import time

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, ROOT)

import jax                      # noqa: E402
import jax.numpy as jnp         # noqa: E402
import numpy as np              # noqa: E402

from scalable_hw_agnostic_inference_tpu.ops import moe  # noqa: E402
from scalable_hw_agnostic_inference_tpu.ops.pallas import moe_ffn  # noqa: E402

HBM_BYTES_PER_S = 819e9         # benchmark/peaks.json, TPU v5 lite
LAYERS = 3

#: name -> rows, top-k, experts, D, F, popularity skew of the crafted
#: routing, and the deviation of the stored bias that skews the whole
#: layer's own router about as far
SHAPES = {
    "kanana-64": (64, 6, 128, 2048, 768, 0.5, 0.04),
    "trinity-32": (32, 8, 128, 2048, 1024, 2.0, 0.15),
    "kanana-8": (8, 6, 128, 2048, 768, 0.5, 0.04),
}
DRY_SHAPES = {"dry-16": (16, 2, 8, 256, 128, 0.5, 0.04)}


def crafted_routing(rows, k, E, skew, seed=0):
    """``sel [rows, k]``: each row's top-k of a shared popularity (``skew``
    x a standard normal an expert) plus its own Gumbel noise."""
    r = np.random.default_rng(seed)
    logits = skew * r.standard_normal(E) + r.gumbel(size=(rows, E))
    return np.argsort(-logits, axis=1)[:, :k].astype(np.int32)


def timed(f, *args, n):
    for _ in range(2):
        jax.block_until_ready(f(*args))
    t0 = time.perf_counter()
    for _ in range(n):
        out = f(*args)
    jax.block_until_ready(out)
    return (time.perf_counter() - t0) / n, out


def chain(product, layers, x, sel, w, sizes):
    for ex in layers:
        x = x + product(ex, x, sel, w, sizes, 0).astype(x.dtype)
    return x


def bench_shape(name, shape, n, dry):
    rows, k, E, D, F, skew, bias_dev = shape
    sel_np = crafted_routing(rows, k, E, skew)
    counts = np.bincount(sel_np.ravel(), minlength=E)
    touched, load_max = int((counts > 0).sum()), int(counts.max())
    keys = jax.random.split(jax.random.PRNGKey(0), 4 * LAYERS + 2)
    leaf = lambda key, s: (jax.random.normal(key, s, jnp.float32)  # noqa: E731
                           * 0.02).astype(jnp.bfloat16)
    layers = [{"gate": leaf(keys[4 * i], (E, D, F)),
               "up": leaf(keys[4 * i + 1], (E, D, F)),
               "down": leaf(keys[4 * i + 2], (E, F, D))}
              for i in range(LAYERS)]
    x = jax.random.normal(keys[-1], (rows, D), jnp.bfloat16)
    sel = jnp.asarray(sel_np)
    w = jnp.full((rows, k), 1.0 / k, jnp.float32)
    sizes = jnp.asarray(counts, jnp.int32)
    expert_bytes = 3 * D * F * 2
    least_s = touched * expert_bytes / HBM_BYTES_PER_S
    base = dict(shape=name, rows=rows, k=k, experts=E, D=D, F=F,
                touched=touched, load_max=load_max,
                load_max_over_mean=load_max / (rows * k / E),
                bytes_ms=least_s * 1e3)
    out = []

    def record(form, seconds, **extra):
        rec = dict(base, form=form, ms_per_layer=seconds / LAYERS * 1e3,
                   share=least_s / (seconds / LAYERS), **extra)
        print(json.dumps(rec), flush=True)
        out.append(rec)
        return rec

    grouped = jax.jit(lambda *a: chain(moe._grouped, *a))
    t, want = timed(grouped, layers, x, sel, w, sizes, n=n)
    record("grouped", t)
    want = np.asarray(want, np.float32)
    tiles = [tf for tf in (F, F // 2, F // 3, 128)
             if tf % 128 == 0 and F % tf == 0]
    for tf in sorted(set(tiles), reverse=True):
        def streamed(ex, x2, sel, w, sizes, first, tf=tf):
            return moe_ffn.moe_streamed_ffn(
                x2, *moe.streamed_operands(sel, w, sizes, first),
                ex["gate"], ex["up"], ex["down"], tile_f=tf)

        try:
            f = jax.jit(lambda *a, s=streamed: chain(s, *a))
            t, got = timed(f, layers, x, sel, w, sizes, n=n)
            err = float(np.abs(np.asarray(got, np.float32) - want).max())
            record(f"streamed-tile{tf}", t, max_abs_diff_vs_grouped=err,
                   out_abs_max=float(np.abs(want).max()))
        except Exception as e:      # a tile Mosaic refuses: say so, go on
            print(json.dumps(dict(base, form=f"streamed-tile{tf}",
                                  error=f"{type(e).__name__}: "
                                        f"{str(e)[:300]}")), flush=True)
    if rows < 16:
        return out
    # the whole layer: router, statistics and the shared expert with it
    from scalable_hw_agnostic_inference_tpu.models.llama import LlamaConfig

    cfg = LlamaConfig.tiny_afmoe()
    cfg = dataclasses.replace(cfg, n_experts=E, n_experts_per_tok=k, dim=D,
                              moe_mlp_dim=F, n_shared_experts=1)
    rk = jax.random.split(jax.random.PRNGKey(1), 5)
    shared = {nm: {"kernel": leaf(kk, s)} for nm, kk, s in (
        ("gate", rk[2], (D, F)), ("up", rk[3], (D, F)),
        ("down", rk[4], (F, D)))}
    mps = [{"router": {"kernel": jax.random.normal(rk[0], (D, E)) * 0.02},
            "bias": jnp.asarray(bias_dev * np.random.default_rng(0)
                                .standard_normal(E), jnp.float32),
            "experts": ex, "shared": shared} for ex in layers]

    for form in ("streamed", "grouped"):
        def whole(mps, x):          # a function a form: jit caches by it
            st = 0
            for mp in mps:
                y, s = moe.expert_layer(mp, x, cfg)
                x, st = x + y, st + s
            return x, st

        orig = moe.expert_form
        if form == "grouped":
            moe.expert_form = lambda n_rows, c: "grouped"
        try:
            t, (_, st) = timed(jax.jit(whole), mps, x, n=n)
        finally:
            moe.expert_form = orig
        record(f"expert_layer-{form}", t,
               routed_touched_mean=float(st[0]) / LAYERS)
    return out


def main():
    dry = "--dry-run" in sys.argv
    if not dry and jax.default_backend() != "tpu":
        sys.exit("moe_bench needs the chip (or --dry-run)")
    out = []
    for name, shape in (DRY_SHAPES if dry else SHAPES).items():
        out += bench_shape(name, shape, n=2 if dry else 40, dry=dry)
    if not dry:
        dev = jax.devices()[0]
        out.append({"device": dev.device_kind, "platform": dev.platform})
        os.makedirs(os.path.join(ROOT, "chiprun_out"), exist_ok=True)
        with open(os.path.join(ROOT, "chiprun_out", "moe_bench.json"),
                  "w") as fh:
            json.dump(out, fh, indent=1)


if __name__ == "__main__":
    main()
