"""Project serving breakpoints from the offline perf model (VERDICT r4 #1/#3).

The committed TPU breakpoint rows were extrapolations from ONE round-2
single-stream bench; this replaces their basis with the deviceless perf
model (PERF_MODEL.json): real XLA:TPU executables' roofline times, scaled by
the calibrated achieved-fraction eta. Rows stay ``projected: true`` until a
measured on-chip ramp (scripts/breaking_point.py) overwrites them; this
script only upgrades the *projection* quality in the meantime.

Projected rows:
  sd21-tpu    one replica at SD_BATCH_MAX=4: RPS = projected b4 coalesced
              throughput (one image per request), p50 = batch seconds
  sd21-tpub8  the batch-8 + flash-attention throughput tier
  vllm-tpu    continuous batching at full occupancy (bs=8), the ramp's
              16-token streamed requests:
              RPS ~ batch / (t_prefill + gen_tokens * t_decode_step),
              TTFT ~ projected prefill time, TPOT ~ decode step / batch row
"""

from __future__ import annotations

import json
import os
import time

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
PERF = os.path.join(ROOT, "PERF_MODEL.json")
BANK = os.path.join(ROOT, "deploy", "breakpoints.json")
GEN_TOKENS = 16   # the vllm ramp payload's max_tokens (breaking_point.py)


def project_rows(perf: dict) -> dict:
    cal = perf.get("calibration") or {}
    eta = cal.get("eta_roofline")
    if not eta:
        raise SystemExit("PERF_MODEL.json has no calibration anchor")
    comp = perf["composed"]
    components = perf["components"]
    out = {}

    def base(basis: str) -> dict:
        return {
            "projected": True,
            "platform": "tpu-v5e-1-projected",
            "basis": f"{basis} (PERF_MODEL.json: XLA:TPU cost analysis / "
                     f"roofline at eta={eta:.3f}, anchored on the r2 on-chip "
                     f"SD single-stream bench). Replaced by a measured ramp "
                     f"when one is run on the chip.",
            "threshold_s": 0.9,
            "commit": "see PERF_MODEL.json",
            "measured_at": time.strftime("%Y-%m-%dT%H:%M:%SZ", time.gmtime()),
        }

    def sd_row(key: str, batch: int, basis: str):
        row = comp.get(key)
        if not row or not row.get("t_roofline_s"):
            return None
        t_call = row["t_roofline_s"] / eta
        r = base(basis)
        # request latency at full coalescer occupancy = one batched call;
        # over the 900 ms SLO is recorded honestly (over_threshold flag)
        r["breakpoint"] = {"rps": round(batch / t_call, 4),
                           "p50": round(t_call, 4),
                           "concurrency": batch, "errors": 0}
        if t_call > r["threshold_s"]:
            r["breakpoint"]["over_threshold_at_c1"] = True
        return r

    # the latency tier serves the measured (non-flash) dispatch, so its
    # projection must use the matching executables
    r = sd_row("sd_b4", 4, "coalesced batch-4 denoise+VAE projection")
    if r:
        out["sd21-tpu"] = r
    r = (sd_row("sd_b8_flash", 8,
                "batch-8 flash-attention throughput-tier projection")
         or sd_row("sd_b8", 8, "batch-8 throughput-tier projection"))
    if r:
        out["sd21-tpub8"] = r

    dec = components.get("vllm_decode_b8")
    pre = components.get("llama1b_prefill")
    if dec and pre and dec.get("t_roofline_s") and pre.get("t_roofline_s"):
        t_dec = dec["t_roofline_s"] / eta
        t_pre = pre["t_roofline_s"] / eta
        batch = dec.get("batch", 8)
        # prefill already yields the FIRST token (scripts/breaking_point.py's
        # TPOT definition): a GEN_TOKENS request pays GEN_TOKENS - 1 decode
        # steps, not GEN_TOKENS
        t_req = t_pre + (GEN_TOKENS - 1) * t_dec   # one batch of requests
        r = base("paged-engine decode (bs=8) + bucketed prefill projection, "
                 f"{GEN_TOKENS}-token streamed requests")
        r["slo"] = "ttfb"
        r["breakpoint"] = {
            "rps": round(batch / t_req, 4),
            "p50": round(t_req, 4),
            "ttfb_p50": round(t_pre, 4),
            "tpot": round(t_dec, 4),
            "concurrency": batch, "errors": 0,
        }
        out["vllm-tpu"] = r
    return out


def main() -> None:
    with open(PERF) as f:
        perf = json.load(f)
    rows = project_rows(perf)
    bank = {}
    if os.path.exists(BANK):
        with open(BANK) as f:
            bank = json.load(f)
    replaced = []
    for key, row in rows.items():
        cur = bank.get(key)
        if cur is not None and not cur.get("projected"):
            # never clobber a MEASURED row with a projection
            continue
        bank[key] = row
        replaced.append(key)
    tmp = f"{BANK}.{os.getpid()}.tmp"
    with open(tmp, "w") as f:
        json.dump(bank, f, indent=1, sort_keys=True)
        f.write("\n")
    os.replace(tmp, BANK)
    print(f"projected rows written: {replaced}")


if __name__ == "__main__":
    main()
