"""KDA's two kernels alone, at Kimi-Linear's widths, each against the
token-by-token recurrence and its own roofline: ``chiprun -- python
scripts/kda_bench.py`` (``--dry-run``: tiny, on the CPU, kernels
interpreted; its times mean nothing).

The chunk kernel over one 2048-token program's tokens (32 heads of 128, one
row, a state that is not zero): its time, and the recurrence's ``6 d^2``
operations a token and head over 197 TFLOP/s as a share of it. The step
kernel at each decode bucket over a 17-slot arena: its time, and the rows'
states read and written (``2 x 32 x 128 x 128 x 4`` B a row) over 819 GB/s
as a share of it. Errors are the largest absolute difference against
``ops.kda.recurrence`` / ``ops.kda.step`` on the same seeded operands
(``ops.kernel_check.kda_cases``); beside the chunk kernel's stands the
recurrence's own with its state rounded to bfloat16 after every token, which
the case's tolerance has to refuse. Writes ``chiprun_out/kda_bench.json``.
"""

import json
import os
import sys
import time

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, ROOT)

import jax                      # noqa: E402
import numpy as np              # noqa: E402

from scalable_hw_agnostic_inference_tpu.ops import kernel_check  # noqa: E402
from scalable_hw_agnostic_inference_tpu.ops.pallas.kda_chunk import (  # noqa: E402
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


def chunk_sizes(H, d, T, dry):
    """The chunk kernel at other chunk sizes than the module's ``CHUNK``
    (patched for the call): what defends the choice. A larger chunk makes
    fewer grid steps and a triangular solve that grows with its square."""
    from scalable_hw_agnostic_inference_tpu.ops.pallas import kda_chunk

    case = kernel_check.kda_cases(H, d, bucket=T, max_num_seqs=4)[0]
    args = jax.jit(case.make_inputs)(jax.random.PRNGKey(0))
    want = case.oracle(*args)
    shipped, out = kda_chunk.CHUNK, []
    for size in (32, 64, 128):
        kda_chunk.CHUNK = size
        f = jax.jit(lambda *a: kda_chunk.kda_chunk_prefill.__wrapped__(
            *a, interpret=dry))
        o, s_end = f(*args)
        got = np.concatenate([np.ravel(o), np.ravel(s_end)])
        rec = {"case": f"{case.name}-chunk{size}", "shipped": size == shipped,
               "max_abs_err": float(np.abs(got - np.ravel(want)).max()),
               "ms": timed(f, args, 2 if dry else 30) * 1e3}
        print(json.dumps(rec), flush=True)
        out.append(rec)
    kda_chunk.CHUNK = shipped
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
