"""The routed experts' product alone, each form against ``ragged_dot``, at
the benchmark cells' shapes: ``chiprun -- python scripts/moe_bench.py``
(``--dry-run``: tiny, on the CPU, kernels interpreted; its times mean
nothing).

First the PREFILL shapes (``--prefill-only`` stops there): the largest
prefill program of each routed configuration (tokens, top-k, the experts
routed over and HELD, the published widths), a routing skewed by a shared
popularity, three expert layers chained in one program, each routing its own
way, so that the device time of a layer is the program's over three. Timed:
the grouped form (sort, gather, three ``ragged_dot``, scatter), the tiled
form whole at each row tile (placement, gather into the layout, kernel,
gather back), its placement alone and its kernel alone; ``share`` is the
HELD experts' bytes at the chip's HBM peak over the time. ``--trace`` adds
the device's self time by op for both forms.

Then the DECODE shapes: rows, top-k and 128 experts of the published widths,
an assignment crafted to touch about as many experts as the cell's counters
read (``experts_touched_mean.moe``, ``expert_load_max_over_mean.moe``).
Timed: the grouped form, the streamed kernel at each inner tile, and the
whole ``expert_layer`` (router and shared expert with it) under each form.
``share`` is the touched experts' bytes at the HBM peak over the time.

Writes ``chiprun_out/moe_bench.json`` (``--prefill-only``:
``chiprun_out/moe_bench_prefill.json``).
"""

import dataclasses
import functools
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

#: the largest prefill program of each routed configuration: name -> rows,
#: top-k, experts routed over, experts HELD (the first so many), D, F, skew
PREFILL_SHAPES = {
    "kimi-2048": (2048, 8, 256, 128, 2304, 1024, 0.5),
    "kanana-2048": (2048, 6, 128, 128, 2048, 768, 0.7),
    "trinity-1024": (1024, 8, 128, 128, 2048, 1024, 1.2),
}
DRY_PREFILL_SHAPES = {"dry-300": (300, 2, 8, 4, 256, 128, 0.7)}
ROW_TILES = (64, 128, 256)


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


def op_times(label, f, *args, runs=5):
    """Device self time by op over ``runs`` calls, from a profiler trace."""
    import glob
    import tempfile

    from benchmark import trace as tr

    d = tempfile.mkdtemp(dir=os.path.join(ROOT, "chiprun_out"))
    jax.block_until_ready(f(*args))
    jax.profiler.start_trace(d)
    for _ in range(runs):
        out = f(*args)
    jax.block_until_ready(out)
    jax.profiler.stop_trace()
    files = sorted(glob.glob(os.path.join(d, "plugins", "profile", "*",
                                          "*.xplane.pb")))
    planes = tr.load_xplane(files[-1])["planes"]
    for pname, lines in planes.items():
        if tr.DEVICE_PLANE.match(pname) and tr.OPS_LINE in lines:
            times = tr.self_times(lines[tr.OPS_LINE])
            top = sorted(times.items(), key=lambda kv: -kv[1])[:14]
            print(json.dumps({"ops_ms_per_layer": label, "top": [
                [k, round(v / runs / LAYERS * 1e3, 4)] for k, v in top],
                "sum": round(sum(times.values()) / runs / LAYERS * 1e3, 4)}),
                flush=True)
            break


def bench_prefill_shape(name, shape, n, trace=False):
    """A prefill program's product: today's grouped form, and the tiled one
    at each row tile, whole (sort, gather into the layout, kernel, sum back)
    and the kernel alone on the first layer's operands. Each of the three
    chained layers routes its own way, so nothing of one layer's sort or
    layout serves another. ``share``: the HELD experts' bytes at the HBM
    peak over the time."""
    rows, k, E, held, D, F, skew = shape
    sels = [crafted_routing(rows, k, E, skew, seed=i) for i in range(LAYERS)]
    counts = [np.bincount(s.ravel(), minlength=E)[:held] for s in sels]
    keys = jax.random.split(jax.random.PRNGKey(0), 4 * LAYERS + 2)
    leaf = lambda key, s: (jax.random.normal(key, s, jnp.float32)  # noqa: E731
                           * 0.02).astype(jnp.bfloat16)
    layers = [{"gate": leaf(keys[4 * i], (held, D, F)),
               "up": leaf(keys[4 * i + 1], (held, D, F)),
               "down": leaf(keys[4 * i + 2], (held, F, D)),
               "sel": jnp.asarray(sels[i]),
               "sizes": jnp.asarray(counts[i], jnp.int32)}
              for i in range(LAYERS)]
    x = jax.random.normal(keys[-1], (rows, D), jnp.bfloat16)
    w = jnp.full((rows, k), 1.0 / k, jnp.float32)
    least_s = held * 3 * D * F * 2 / HBM_BYTES_PER_S
    mine = int(np.mean([c.sum() for c in counts]))
    base = dict(shape=name, rows=rows, k=k, experts=E, held=held, D=D, F=F,
                assignments_held=mine,
                load_max=int(max(c.max() for c in counts)),
                load_max_over_mean=float(np.mean(
                    [c.max() / (c.sum() / held) for c in counts])),
                bytes_ms=least_s * 1e3,
                flops_ms=mine * 3 * 2 * D * F / 197e12 * 1e3)
    out = []

    def record(form, seconds, layers_timed=LAYERS, **extra):
        rec = dict(base, form=form,
                   ms_per_layer=seconds / layers_timed * 1e3,
                   share=least_s / (seconds / layers_timed), **extra)
        print(json.dumps(rec), flush=True)
        out.append(rec)

    def chained(product):
        def run(layers, x, w):
            for ex in layers:
                x = x + product(ex, x, ex["sel"], w, ex["sizes"],
                                0).astype(x.dtype)
            return x
        return jax.jit(run)

    grouped = chained(moe._grouped)
    t, want = timed(grouped, layers, x, w, n=n)
    record("grouped", t)
    want = np.asarray(want, np.float32)
    for tm in ROW_TILES:
        tag = f"rows{tm}"

        def operands(sel, sizes, tm=tm):
            return moe.tiled_operands(sel, sizes, 0, tm)

        try:
            f = chained(functools.partial(moe._tiled, tile_rows=tm))
            t, got = timed(f, layers, x, w, n=n)
            err = float(np.abs(np.asarray(got, np.float32) - want).max())
            ex = layers[0]
            t2, (tok, te, nt, _, _) = timed(
                jax.jit(operands), ex["sel"], ex["sizes"], n=n)
            record(f"tiled-{tag}", t, max_abs_diff_vs_grouped=err,
                   out_abs_max=float(np.abs(want).max()), tiles=int(nt),
                   tiles_bound=int(te.shape[0]))
            record(f"tiled-operands-alone-{tag}", t2, layers_timed=1)
            t, _ = timed(moe_ffn.moe_tiled_ffn, x[tok], te, nt, ex["gate"],
                         ex["up"], ex["down"], n=n)
            record(f"tiled-kernel-alone-{tag}", t, layers_timed=1)
            if trace and tm == 128:
                op_times(f"tiled-{tag}", f, layers, x, w)
        except Exception as e:      # a tile Mosaic refuses: say so, go on
            print(json.dumps(dict(base, form=f"tiled-{tag}",
                                  error=f"{type(e).__name__}: "
                                        f"{str(e)[:300]}")), flush=True)
    if trace:
        op_times("grouped", grouped, layers, x, w)
    return out


def main():
    dry = "--dry-run" in sys.argv
    if not dry and jax.default_backend() != "tpu":
        sys.exit("moe_bench needs the chip (or --dry-run)")
    out = []
    for name, shape in (DRY_PREFILL_SHAPES if dry
                        else PREFILL_SHAPES).items():
        out += bench_prefill_shape(name, shape, n=2 if dry else 20,
                                   trace="--trace" in sys.argv)
    if "--prefill-only" in sys.argv:
        if not dry:
            write_out(out, "moe_bench_prefill.json")
        return
    for name, shape in (DRY_SHAPES if dry else SHAPES).items():
        out += bench_shape(name, shape, n=2 if dry else 40, dry=dry)
    if not dry:
        write_out(out, "moe_bench.json")


def write_out(out, name):
    dev = jax.devices()[0]
    out.append({"device": dev.device_kind, "platform": dev.platform})
    print(json.dumps(out[-1]))
    os.makedirs(os.path.join(ROOT, "chiprun_out"), exist_ok=True)
    with open(os.path.join(ROOT, "chiprun_out", name), "w") as fh:
        json.dump(out, fh, indent=1)


if __name__ == "__main__":
    main()
