"""The state-space mixer's two kernels alone, at Nemotron-3-Nano's widths,
each against the token-by-token recurrence and its own roofline, and the two
expert kernels at its experts' form (two matrices of 2688 x 1856, ``relu **
2``, 64 of 128 held) against the plain grouped form: ``chiprun -- python
scripts/ssm_bench.py`` (``--dry-run``: tiny, on the CPU, kernels
interpreted; its times mean nothing).

The chunk kernel over the largest prefill program's tokens (4 rows of 512,
64 heads of 64 x 128, a state that is not zero): its time, and the
recurrence's ``4 P N`` operations a token and head over 197 TFLOP/s as a
share of it. The step kernel at EVERY decode bucket the engine warms (1, 2,
4, ... 128 rows, all live) over a 129-slot arena, eight steps chained in
one program: its time beside its bytes' time (the rows' states read and
written, ``2 x 64 x 64 x 128 x 4`` B a row, over 819 GB/s) and the share;
its ``ssm_cases`` (two padded rows, one row, half the rows padded) are
compared with the oracle first.
The streamed expert kernel at 128 rows and at 8, and the tiled one at 2,048
rows: each one's time beside the plain form's on the same assignments, and
the bytes of the held experts touched over 819 GB/s as a share of it. Errors
are the largest absolute difference against the oracle on the same seeded
operands (``ops.kernel_check``). Writes ``chiprun_out/ssm_bench.json``.
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

from scalable_hw_agnostic_inference_tpu.ops import kernel_check, moe  # noqa: E402
from scalable_hw_agnostic_inference_tpu.ops.pallas.ssm_chunk import (  # noqa: E402
    ssm_chunk_prefill,
)
from scalable_hw_agnostic_inference_tpu.ops.pallas.ssm_step import (  # noqa: E402
    ssm_decode_step,
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


def _time_step(case, dry, n):
    """One step's time at a case's rows: the arena donated and handed on,
    as the engine's step does, and STEPS steps chained in one program, so
    that the device's time is read and not the host's dispatch."""
    def chain(x, Bm, Cm, dt, ld, arena, ids):
        for _ in range(STEPS):
            y, arena = ssm_decode_step(x, Bm, Cm, dt, ld, arena, ids,
                                       interpret=dry)
            x = x + 0.0 * y        # a step needs the one before
        return y, arena

    f = jax.jit(chain, donate_argnums=(5,))
    *ops, arena, ids = jax.jit(case.make_inputs)(jax.random.PRNGKey(0))
    for _ in range(2):
        _, arena = f(*ops, arena, ids)
    jax.block_until_ready(arena)
    t0 = time.perf_counter()
    for _ in range(n):
        _, arena = f(*ops, arena, ids)
    jax.block_until_ready(arena)
    return (time.perf_counter() - t0) / n / STEPS * 1e3


def mixer_kernels(dry):
    H, P, N, G = (4, 16, 32, 2) if dry else (64, 64, 128, 8)
    T, rows_p, slots = (160, 2, 4) if dry else (512, 4, 128)
    n = 2 if dry else 30
    out = []
    for case in kernel_check.ssm_cases(H, P, N, G, bucket=T,
                                       prefill_rows=rows_p,
                                       max_num_seqs=slots):
        rec = {"case": case.name, "tol": case.tol,
               "max_abs_err": case.max_abs_err(interpret=dry)}
        if "chunk" in case.name:
            args = jax.jit(case.make_inputs)(jax.random.PRNGKey(0))
            f = jax.jit(lambda *a: ssm_chunk_prefill(*a, interpret=dry))
            rec["ms"] = timed(f, args, n) * 1e3
            least = 4.0 * H * P * N * T * args[0].shape[0] / MXU_FLOPS_PER_S
            rec["roofline_share"] = least / (rec["ms"] / 1e3)
        rec["ok"] = rec["max_abs_err"] <= case.tol
        print(json.dumps(rec), flush=True)
        out.append(rec)
    # the step kernel at EVERY decode bucket the engine warms, all rows live
    rows = 1
    while rows <= slots:
        case = kernel_check._ssm_step_case(H, P, N, G, rows, slots, padded=0)
        rec = {"case": case.name, "rows": rows,
               "ms": _time_step(case, dry, n),
               "bytes_ms": 2.0 * H * P * N * 4 * rows / HBM_BYTES_PER_S * 1e3}
        rec["roofline_share"] = rec["bytes_ms"] / rec["ms"]
        print(json.dumps(rec), flush=True)
        out.append(rec)
        rows *= 2
    return out


def expert_kernels(dry):
    """The streamed and the tiled kernel on relu ** 2 experts of two
    matrices, 64 of 128 held, against the plain (``ragged_dot``) form."""
    E, k, D, F, held = (8, 3, 128, 192, 4) if dry else (128, 6, 2688, 1856,
                                                        64)
    n = 2 if dry else 20
    first = E - held
    key = jax.random.PRNGKey(1)
    out = []
    for rows, form in ((8, "streamed"), (16 if dry else 128, "streamed"),
                       (64 if dry else 2048, "tiled")):
        x, sel, w, _gate, up, down = jax.jit(
            lambda k_: kernel_check._expert_inputs(
                k_, n_experts=E, held=held, top_k=k, D=D, F=F, rows=rows,
                x_std=0.5)
        )(key)
        ex = kernel_check._expert_leaves("relu2", _gate, up, down)
        kw = {"interpret": True} if dry and form == "tiled" else {}

        def run(fn, **kw_):
            return jax.jit(lambda x_, sel_, w_, ex_: fn(
                ex_, x_, sel_, w_,
                moe.expert_counts(sel_, E)[first:], first, "relu2", **kw_))

        fast, plain = run(moe._FORMS[form], **kw), run(moe._grouped)
        args = (x, sel, w, ex)
        got, want = fast(*args), plain(*args)
        touched = int(jnp.sum(moe.expert_counts(sel, E)[first:] > 0))
        rec = {"case": f"experts-{form}-E{held}of{E}k{k}-D{D}-F{F}-b{rows}"
                       "-relu2",
               "tol": kernel_check.TOL_EXPERTS,
               "max_abs_err": float(jnp.max(jnp.abs(got - want))),
               "held_touched": touched,
               "ms": timed(fast, args, n) * 1e3,
               "plain_ms": timed(plain, args, n) * 1e3}
        least = touched * 2.0 * D * F * up.dtype.itemsize / HBM_BYTES_PER_S
        rec["roofline_share"] = least / (rec["ms"] / 1e3)
        rec["ok"] = rec["max_abs_err"] <= rec["tol"]
        print(json.dumps(rec), flush=True)
        out.append(rec)
    return out


def main():
    dry = "--dry-run" in sys.argv
    if not dry and jax.default_backend() != "tpu":
        sys.exit("ssm_bench needs the chip (or --dry-run)")
    out = mixer_kernels(dry) + expert_kernels(dry)
    if not dry:
        dev = jax.devices()[0]
        out.append({"device": dev.device_kind, "platform": dev.platform})
        os.makedirs(os.path.join(ROOT, "chiprun_out"), exist_ok=True)
        with open(os.path.join(ROOT, "chiprun_out", "ssm_bench.json"),
                  "w") as fh:
            json.dump(out, fh, indent=1)
    sys.exit(0 if all(r.get("ok", True) for r in out) else 1)


if __name__ == "__main__":
    main()
