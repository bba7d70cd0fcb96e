"""KDA's two kernels alone, at Kimi-Linear's widths, each against the
token-by-token recurrence and its own roofline: ``chiprun -- python
scripts/kda_bench.py`` (``--dry-run``: tiny, on the CPU, kernels
interpreted; its times mean nothing).

The chunk kernel over one 2048-token program's tokens (32 heads of 128, one
row, a state that is not zero): the whole call's time, the recurrence's
``6 d^2`` operations a token and head over 197 TFLOP/s as a share of it, and
the device's time by op from a trace (the kernel beside what XLA puts round
it). The step
kernel at each decode bucket over a 17-slot arena: its time, and the rows'
states read and written (``2 x 32 x 128 x 128 x 4`` B a row) over 819 GB/s
as a share of it. Errors are the largest absolute difference against
``ops.kda.recurrence`` / ``ops.kda.step`` on the same seeded operands
(``ops.kernel_check.kda_cases``); beside the chunk kernel's stands the
recurrence's own with its state rounded to bfloat16 after every token, which
the case's tolerance has to refuse. Then what defends the module's
constants: the chunk kernel at other chunk sizes than ``CHUNK``, and 1, 2, 4
and 8 heads a grid step on a head-major twin of the kernel (the shipped
layout's blocks hold whole tiles of 8 heads, so it cannot take fewer; the
twin pays transposes round it, so it is the KERNEL's own time from the trace
that is compared). Last, the bits: the three-pass mask product against the
six passes of ``Precision.HIGHEST`` inside a compiled kernel (held to be the
same number), and each stacked product against the two it replaced (counted,
not held: a product's rows meet the MXU's accumulator in another order).
Writes ``chiprun_out/kda_bench.json``.
"""

import json
import os
import sys
import time

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, ROOT)

import jax                      # noqa: E402
import jax.numpy as jnp         # noqa: E402
import numpy as np              # noqa: E402
from jax.experimental import pallas as pl  # noqa: E402

from scalable_hw_agnostic_inference_tpu.ops import kda, kernel_check  # noqa: E402
from scalable_hw_agnostic_inference_tpu.ops.pallas.kda_chunk import (  # noqa: E402
    HEAD_GROUP,
    kda_chunk_prefill,
)
from scalable_hw_agnostic_inference_tpu.ops.pallas.kda_step import (  # noqa: E402
    kda_decode_step,
)

MXU_FLOPS_PER_S, HBM_BYTES_PER_S = 197e12, 819e9   # benchmark/peaks.json
STEPS = 8


def timed(f, args, n):
    for _ in range(2):
        jax.block_until_ready(f(*args))
    t0 = time.perf_counter()
    for _ in range(n):
        out = f(*args)
    jax.block_until_ready(out)
    return (time.perf_counter() - t0) / n


def op_times(f, args, runs=5):
    """Device self time by op of ``f(*args)``, ms a call, from a profiler
    trace: the kernel beside whatever XLA puts round it."""
    import glob
    import tempfile

    from benchmark import trace as tr

    os.makedirs(os.path.join(ROOT, "chiprun_out"), exist_ok=True)
    jax.block_until_ready(f(*args))
    # the trace is tens of megabytes: read, it is not kept
    with tempfile.TemporaryDirectory(
            dir=os.path.join(ROOT, "chiprun_out")) as d:
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
            return {k: round(v / runs * 1e3, 4) for k, v in sorted(
                times.items(), key=lambda kv: -kv[1])[:8]}
    return {}


def _refused(e):
    """A block Mosaic has no VMEM for, as a record's field; anything else
    is a fault and is raised."""
    if "RESOURCE_EXHAUSTED" not in str(e):
        raise e
    return f"{type(e).__name__}: {str(e)[:300]}"


def _case(H, d, T):
    case = kernel_check.kda_cases(H, d, bucket=T, max_num_seqs=4)[0]
    args = jax.jit(case.make_inputs)(jax.random.PRNGKey(0))
    return case, args, np.ravel(case.oracle(*args))


def _err(out, want):
    return float(np.abs(np.concatenate([np.ravel(a) for a in out])
                        - want).max())


def chunk_sizes(H, d, T, dry):
    """The chunk kernel at other chunk sizes than the module's ``CHUNK``
    (patched for the call): what defends the choice. A larger chunk makes
    fewer grid steps and a triangular solve that grows with its square."""
    from scalable_hw_agnostic_inference_tpu.ops.pallas import kda_chunk

    case, args, want = _case(H, d, T)
    shipped, out = kda_chunk.CHUNK, []
    for size in (32, 64, 128):
        kda_chunk.CHUNK = size
        f = jax.jit(lambda *a: kda_chunk.kda_chunk_prefill.__wrapped__(
            *a, interpret=dry))
        rec = {"case": f"{case.name}-chunk{size}", "shipped": size == shipped}
        try:
            rec["max_abs_err"] = _err(f(*args), want)
        except jax.errors.JaxRuntimeError as e:
            rec["refused"] = _refused(e)
        else:
            steps = -(-H // min(H, HEAD_GROUP)) * -(-T // size)
            rec["ms"] = timed(f, args, 2 if dry else 30) * 1e3
            rec["us_per_grid_step"] = rec["ms"] * 1e3 / steps
        print(json.dumps(rec), flush=True)
        out.append(rec)
    kda_chunk.CHUNK = shipped
    return out


TWIN_NAME = "kda_chunk_head_major"


def head_major_twin(hg, dry):
    """The chunk kernel on HEAD-MAJOR operands ``[B, H, T, d]``, ``hg`` heads
    a grid step: the same body (``ops.kda.chunk_math``), blocks ``(hg,
    CHUNK, d)`` that hold whole tiles at any ``hg``, the operands transposed
    and ``kb``, ``vb`` formed outside (as the kernel did before it took a
    group of heads on the layer's own layout)."""
    from jax.experimental.pallas import tpu as pltpu

    C = kda.CHUNK

    def kernel(q_ref, k_ref, kb_ref, vb_ref, g_ref, s0_ref, o_ref, s_ref,
               st_ref):
        c = pl.program_id(2)

        @pl.when(c == 0)
        def _enter():
            st_ref[...] = s0_ref[...]

        o, st = kda.chunk_math(q_ref[...], k_ref[...], kb_ref[...],
                               vb_ref[...], g_ref[...], st_ref[...])
        o_ref[...] = o
        st_ref[...] = st

        @pl.when(c == pl.num_programs(2) - 1)
        def _leave():
            s_ref[...] = st

    def call(q, k, v, g, beta, s0):
        B, T, H, d = q.shape
        hm = lambda a: jnp.moveaxis(a, 2, 1)                  # noqa: E731
        b = beta[..., None]
        tok = pl.BlockSpec((None, hg, C, d), lambda i, h, c: (i, h, c, 0))
        state = pl.BlockSpec((None, hg, d, d), lambda i, h, c: (i, h, 0, 0))
        o, s = pl.pallas_call(
            kernel, grid=(B, H // hg, T // C),
            in_specs=[tok] * 5 + [state], out_specs=[tok, state],
            out_shape=[jax.ShapeDtypeStruct((B, H, T, d), jnp.float32),
                       jax.ShapeDtypeStruct((B, H, d, d), jnp.float32)],
            scratch_shapes=[pltpu.VMEM((hg, d, d), jnp.float32)],
            compiler_params=pltpu.CompilerParams(
                dimension_semantics=("parallel", "parallel", "arbitrary")),
            interpret=dry, name=TWIN_NAME,
        )(hm(q), hm(k), hm(k * b), hm(v * b), hm(g), s0)
        return jnp.moveaxis(o, 1, 2), s

    return jax.jit(call)


def head_groups(H, d, T, dry):
    """1, 2, 4 and 8 heads a grid step on the head-major twin: what defends
    a GROUP of heads a step, and that 8 (the one group the shipped layout
    can take) gives nothing away against 4. One head a step is a chain of
    dependent products the MXUs wait on; a group's chains interleave until
    the MXUs are busy. ``kernel_ms`` is the twin's own device time from a
    trace (``ms``, the call's, holds the same transposes at every group)."""
    case, args, want = _case(H, d, T)
    chunks = args[0].shape[0] * (T // kda.CHUNK)
    out = []
    for hg in (1, 2, 4, 8):
        if H % hg:
            continue
        f = head_major_twin(hg, dry)
        rec = {"case": f"{case.name}-head-major-heads{hg}"}
        try:
            rec["max_abs_err"] = _err(f(*args), want)
        except jax.errors.JaxRuntimeError as e:
            rec["refused"] = _refused(e)
        else:
            rec["ms"] = timed(f, args, 2 if dry else 30) * 1e3
            if not dry:
                rec["kernel_ms"] = op_times(f, args).get(TWIN_NAME)
                rec["us_per_grid_step"] = (
                    rec["kernel_ms"] * 1e3 / (chunks * (H // hg)))
        print(json.dumps(rec), flush=True)
        out.append(rec)
    return out


def _bits(a, b):
    a, b = np.asarray(a), np.asarray(b)
    return {"differ": int((a.view(np.uint32) != b.view(np.uint32)).sum()),
            "of": int(a.size), "max_abs_diff": float(np.abs(a - b).max()),
            "max_abs": float(np.abs(a).max())}


def product_bits(d, dry):
    """Inside compiled kernels, on seeded operands of a chunk's shapes: the
    cumulative decay by ``ops.kda._mask_mm`` (three one-pass products of
    the operand's bfloat16 parts) against the 0/1 mask's product at
    ``Precision.HIGHEST`` (``ok`` only if no bit differs, at every scale of
    ``g``); a block's stacked ``[kb e ; q e]`` product and the stacked ``[kb
    ; q] e^G`` product against the state, each against the two products it
    replaced; and, in a kernel of their own (so that the products have no
    other user), what the body makes of the latter, ``u = inv (vb - .)`` and
    ``o = . + P u``, both ways: a sum with a product can be lowered onto the
    MXU's accumulator, and whether it is may hang on where its other term
    comes from. The stacked forms are counted, not judged."""
    C, B = kda.CHUNK, kda.BLOCK
    n = 4

    def products(g_ref, a_ref, b_ref, kn_ref, st_ref, six_ref, three_ref,
                 rows2_ref, rows1_ref, state2_ref, state1_ref):
        row = jax.lax.broadcasted_iota(jnp.int32, (C, C), 0)
        col = jax.lax.broadcasted_iota(jnp.int32, (C, C), 1)
        g = g_ref[...]
        six_ref[...] = kda._mm(jnp.broadcast_to(
            (col <= row).astype(jnp.float32), g.shape[:-2] + (C, C)), g)
        three_ref[...] = kda._mask_mm(col <= row, g)
        a, b, kn, st = a_ref[...], b_ref[...], kn_ref[...], st_ref[...]
        rows2_ref[...] = jnp.concatenate(
            [kda._mm_nt(a[:, :B], kn), kda._mm_nt(b[:, :B], kn)], axis=-2)
        rows1_ref[...] = kda._mm_nt(
            jnp.concatenate([a[:, :B], b[:, :B]], axis=-2), kn)
        state2_ref[...] = jnp.concatenate(
            [kda._mm_nt(a, st), kda._mm_nt(b, st)], axis=-2)
        state1_ref[...] = kda._mm_nt(jnp.concatenate([a, b], axis=-2), st)

    def users(a_ref, b_ref, kn_ref, st_ref, u2_ref, o2_ref, u1_ref, o1_ref):
        # ``k e^-``'s seeded block stands in for ``vb``, its strictly lower
        # triangle's products for ``inv`` and ``P``
        row = jax.lax.broadcasted_iota(jnp.int32, (C, C), 0)
        col = jax.lax.broadcasted_iota(jnp.int32, (C, C), 1)
        a, b, kn, st = a_ref[...], b_ref[...], kn_ref[...], st_ref[...]
        low = jnp.where(col < row, kda._mm_nt(kn, kn) * (1.0 / d), 0.0)
        u2 = kda._mm(low, kn - kda._mm_nt(a, st))
        u2_ref[...] = u2
        o2_ref[...] = kda._mm_nt(b, st) + kda._mm(low, u2)
        both = kda._mm_nt(jnp.concatenate([a, b], axis=-2), st)
        u1 = kda._mm(low, kn - both[:, :C])
        u1_ref[...] = u1
        o1_ref[...] = both[:, C:] + kda._mm(low, u1)

    shapes = lambda *ss: [jax.ShapeDtypeStruct(sh, jnp.float32)  # noqa: E731
                          for sh in ss]
    f = pl.pallas_call(products, interpret=dry, out_shape=shapes(
        *[(n, C, d)] * 2, *[(n, 2 * B, C)] * 2, *[(n, 2 * C, d)] * 2))
    f_users = pl.pallas_call(users, interpret=dry,
                             out_shape=shapes(*[(n, C, d)] * 4))
    names = ("mask-product-three-passes-vs-highest",
             "stacked-rows-product-vs-two", "stacked-state-product-vs-two",
             "stacked-state-product-u-vs-two",
             "stacked-state-product-o-vs-two")
    recs = {name: [] for name in names}
    for seed in range(6):
        kg, ka, kb, kk, ks = jax.random.split(jax.random.PRNGKey(seed), 5)
        g = -jnp.abs(jax.random.normal(kg, (n, C, d))) * 10.0 ** (seed - 3)
        ops = (jax.random.normal(ka, (n, C, d)),
               jax.random.normal(kb, (n, C, d)),
               jax.random.normal(kk, (n, C, d)),
               jax.random.normal(ks, (n, d, d)))
        got = f(g, *ops)
        u2, o2, u1, o1 = f_users(*ops)
        for name, pair in zip(names, (got[0:2], got[2:4], got[4:6],
                                      (u2, u1), (o2, o1))):
            recs[name].append(_bits(*pair))
    out = [dict(case=name, **max(recs[name], key=lambda r: r["differ"]))
           for name in names]
    # the CPU sums a float32 product in another order: the claim is the chip's
    out[0]["ok"] = dry or out[0]["differ"] == 0
    for rec in out:
        print(json.dumps(rec), flush=True)
    return out


def main():
    dry = "--dry-run" in sys.argv
    if not dry and jax.default_backend() != "tpu":
        sys.exit("kda_bench needs the chip (or --dry-run)")
    H, d, T = (2, 16, 128) if dry else (32, 128, 2048)
    slots = 4 if dry else 16
    out = []
    for case in kernel_check.kda_cases(H, d, bucket=T, max_num_seqs=slots):
        args = jax.jit(case.make_inputs)(jax.random.PRNGKey(0))
        rows = args[0].shape[0]
        rec = {"case": case.name, "tol": case.tol,
               "max_abs_err": case.max_abs_err(interpret=dry)}
        n = 2 if dry else 30
        if "chunk" in case.name:
            f = jax.jit(lambda *a: kda_chunk_prefill(*a, interpret=dry))
            rec["ms"] = timed(f, args, n) * 1e3
            if not dry:
                rec["device_ops_ms"] = op_times(f, args)
        else:
            # the arena donated and handed on, as the engine's step does: a
            # call that keeps its input pays a copy of all 17 slots
            # and STEPS steps chained in one program, so that the device's
            # time is read and not the host's 0.3 ms a dispatch
            def chain(q, k, v, g, beta, arena, ids):
                for _ in range(STEPS):
                    o, arena = kda_decode_step(q, k, v, g, beta, arena, ids,
                                               interpret=dry)
                    q = q + 0.0 * o        # a step needs the one before
                return o, arena

            f = jax.jit(chain, donate_argnums=(5,))
            *ops, arena, ids = args
            for _ in range(2):
                _, arena = f(*ops, arena, ids)
            jax.block_until_ready(arena)
            t0 = time.perf_counter()
            for _ in range(n):
                _, arena = f(*ops, arena, ids)
            jax.block_until_ready(arena)
            rec["ms"] = (time.perf_counter() - t0) / n / STEPS * 1e3
        if "chunk" in case.name:
            least = 6.0 * H * d * d * T * rows / MXU_FLOPS_PER_S
        else:
            least = 2.0 * H * d * d * 4 * rows / HBM_BYTES_PER_S
        rec["roofline_share"] = least / (rec["ms"] / 1e3)
        rec["ok"] = rec["max_abs_err"] <= case.tol
        if "chunk" in case.name:
            # the precision control: the tolerance has to refuse it
            rec["state_bf16_max_abs_err"] = kernel_check.kda_state_bf16_err(
                case)
            rec["ok"] = rec["ok"] and rec["state_bf16_max_abs_err"] > case.tol
        print(json.dumps(rec), flush=True)
        out.append(rec)
    out += chunk_sizes(H, d, T, dry)
    out += head_groups(H, d, T, dry)
    out += product_bits(d, dry)
    if not dry:
        dev = jax.devices()[0]
        out.append({"device": dev.device_kind, "platform": dev.platform})
        os.makedirs(os.path.join(ROOT, "chiprun_out"), exist_ok=True)
        with open(os.path.join(ROOT, "chiprun_out", "kda_bench.json"),
                  "w") as fh:
            json.dump(out, fh, indent=1)
    sys.exit(0 if all(r.get("ok", True) for r in out) else 1)


if __name__ == "__main__":
    main()
