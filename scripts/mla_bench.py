"""The latent decode kernel alone, at Kanana-2's and Kimi-Linear's widths (32
heads, rank 512, 640 lanes, blocks of 16): ``chiprun -- python
scripts/mla_bench.py`` (``--dry-run``: tiny, on the CPU, the kernel
interpreted; its times mean nothing).

First ``ops.kernel_check.latent_cases`` at 64 rows, compiled on the device,
each against its oracle on the same seeded operands (``chip_smoke.py`` never
runs them): the largest absolute difference beside the case's tolerance.
Then the kernel's time at 64 rows over a pool of 10,241 blocks, tables
shuffled: with the rows' lengths drawn as ``decode-sat-8k`` draws them (a
prompt log-uniform in 2,048-8,192 and a uniform part of an answer of
1,024-2,048: mean 5.2k), and with every row at 10,000. ``STEPS`` calls are
chained in one program, so the device's time is read and not the host's
dispatch; the kernel's own time by the device's trace stands beside it. For
each: ms a call, us per 1,024 visible tokens, and the 576 values a visible
token must be read for (``benchmark/shapes_mla.py``) over 819 GB/s as a
share of the time. Writes ``chiprun_out/mla_bench.json``.
"""

import glob
import json
import os
import sys
import tempfile
import time

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, ROOT)

import jax                      # noqa: E402
import jax.numpy as jnp         # noqa: E402
import numpy as np              # noqa: E402

from scalable_hw_agnostic_inference_tpu.ops import kernel_check  # noqa: E402
from scalable_hw_agnostic_inference_tpu.ops.pallas.mla_paged_attention import (  # noqa: E402
    KERNEL_NAME,
    mla_paged_decode,
)

HBM_BYTES_PER_S = 819e9          # benchmark/peaks.json
VALUES = 576                     # rank 512 + the rotary key's 64
STEPS = 8


def cell_lengths(rows, top, seed):
    """Row lengths as the saturated cell holds them: a prompt log-uniform
    in a quarter of ``top`` to ``top``, and a uniform part of an answer of
    an eighth to a quarter of ``top``."""
    rng = np.random.default_rng(seed)
    prompt = np.exp(rng.uniform(np.log(top / 4), np.log(top), rows))
    answer = rng.uniform(top / 8, top / 4, rows) * rng.uniform(0, 1, rows)
    return (prompt + answer).astype(np.int32)


def operands(H, width, block_size, blocks_per_seq, lengths, seed=0):
    """Absorbed queries, a pool every row owns distinct shuffled blocks of,
    the rows' tables (0 past a row's last block) and lengths."""
    rows = len(lengths)
    n_blocks = rows * blocks_per_seq + 1
    kq, kc, kt = jax.random.split(jax.random.PRNGKey(seed), 3)
    q = jax.random.normal(kq, (rows, H, width), jnp.bfloat16)
    pool = jax.random.normal(kc, (n_blocks, block_size, width), jnp.bfloat16)
    tables = 1 + jax.random.permutation(kt, n_blocks - 1).reshape(
        rows, blocks_per_seq).astype(jnp.int32)
    n = jnp.asarray(lengths, jnp.int32)
    held = jnp.arange(blocks_per_seq)[None, :] * block_size < n[:, None]
    return q, pool, jnp.where(held, tables, 0), n


def chained(kernel, rank):
    """``STEPS`` calls in one program, each needing the one before (a loop:
    the kernel is compiled once)."""
    def run(q, pool, tables, n):
        def step(_, q):
            u = kernel(q, pool, tables, n)
            return q.at[..., :rank].add(0 * u)
        return jax.lax.fori_loop(0, STEPS, step, q)
    return jax.jit(run)


def host_ms(f, args, n):
    for _ in range(2):
        jax.block_until_ready(f(*args))
    t0 = time.perf_counter()
    for _ in range(n):
        out = f(*args)
    jax.block_until_ready(out)
    return (time.perf_counter() - t0) / n / STEPS * 1e3


def device_ms(f, args, runs=3):
    """The kernel's own time a call by the device's trace."""
    from benchmark import trace as tr

    os.makedirs(os.path.join(ROOT, "chiprun_out"), exist_ok=True)
    with tempfile.TemporaryDirectory(
            dir=os.path.join(ROOT, "chiprun_out")) as d:
        jax.profiler.start_trace(d)
        try:
            for _ in range(runs):
                out = f(*args)
            jax.block_until_ready(out)
        finally:
            jax.profiler.stop_trace()
        files = sorted(glob.glob(os.path.join(d, "plugins", "profile", "*",
                                              "*.xplane.pb")))
        planes = tr.load_xplane(files[-1])["planes"]
    for pname, lines in planes.items():
        if tr.DEVICE_PLANE.match(pname) and tr.OPS_LINE in lines:
            times = tr.self_times(lines[tr.OPS_LINE])
            if KERNEL_NAME in times:
                return times[KERNEL_NAME] / (runs * STEPS) * 1e3
    return None


def time_kernel(f, name, lengths, *, H, width, block_size, blocks_per_seq,
                n, trace, **_):
    """``f``: the chained kernel (``chained``), one program for every set
    of lengths."""
    args = operands(H, width, block_size, blocks_per_seq, lengths)
    visible = int(np.sum(lengths))
    rec = {"timing": name, "rows": len(lengths), "visible_tokens": visible,
           "ms_host_clock": host_ms(f, args, n)}
    if trace:
        rec["ms_device_trace"] = device_ms(f, args)
    ms = rec.get("ms_device_trace") or rec["ms_host_clock"]
    rec["us_per_1024_tokens"] = ms * 1e3 / (visible / 1024)
    rec["share_of_the_576_values_time"] = (
        visible * VALUES * 2 / HBM_BYTES_PER_S) / (ms / 1e3)
    return rec


def main():
    dry = "--dry-run" in sys.argv
    if not dry and jax.default_backend() != "tpu":
        sys.exit("mla_bench needs the chip (or --dry-run)")
    if dry:
        shape = dict(H=4, width=128, rank=32, block_size=8, blocks_per_seq=40)
        cases = kernel_check.latent_cases(
            4, 128, 64, 128, 32, block_size=8, buckets=(16,),
            max_model_len=320, max_num_seqs=5)
        rows, top, n = 5, 256, 1
    else:
        shape = dict(H=32, width=640, rank=512, block_size=16,
                     blocks_per_seq=640)
        cases = kernel_check.latent_cases(32, 192, 128, 640, 512,
                                          max_num_seqs=64)
        rows, top, n = 64, 8192, 20
    out = []
    for case in cases:
        if not case.name.startswith("mla-"):
            continue
        rec = {"case": case.name, "tol": case.tol,
               "max_abs_err": case.max_abs_err(interpret=dry)}
        rec["ok"] = rec["max_abs_err"] <= case.tol
        print(json.dumps(rec), flush=True)
        out.append(rec)
    kernel = lambda q, pool, tables, lens: mla_paged_decode(   # noqa: E731
        q, pool, tables, lens, rank=shape["rank"], scale=0.07,
        interpret=dry)
    full = shape["block_size"] * shape["blocks_per_seq"]
    f = chained(kernel, shape["rank"])
    for name, lengths in (
            ("cell", cell_lengths(rows, top, seed=2352)),
            ("10k", np.full(rows, min(10000, full), np.int32))):
        rec = time_kernel(f, name, np.minimum(lengths, full), n=n,
                          trace=not dry, **shape)
        print(json.dumps(rec), flush=True)
        out.append(rec)
    if not dry:
        dev = jax.devices()[0]
        out.append({"device": dev.device_kind, "platform": dev.platform})
        os.makedirs(os.path.join(ROOT, "chiprun_out"), exist_ok=True)
        with open(os.path.join(ROOT, "chiprun_out", "mla_bench.json"),
                  "w") as fh:
            json.dump(out, fh, indent=1)
    sys.exit(0 if all(r.get("ok", True) for r in out) else 1)


if __name__ == "__main__":
    main()
