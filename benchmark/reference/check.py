"""Hold the served path to the plain reference, at the size it is served.

During set-up, for each of a few seeded prompts: the engine generates
greedily with its top-k log-probabilities (prefill's last position, then
every decode step through the paged cache); the reference then runs ONE full
forward pass over prompt + generated tokens (teacher forcing: the tokens the
engine chose) and its log-softmax at the same positions is compared with
what the engine reported. Logits, not tokens: with random weights the top
token flips on rounding.

The weights are data: the reference reads the engine's own parameter leaves,
one layer at a time, so it runs at published width and depth beside the
loaded engine (about one layer of float32 transient at a time).
"""

from __future__ import annotations

import json
import math
import os
import time
from typing import Any, Callable, Dict, List

import jax.numpy as jnp
import numpy as np

from . import mistral

HERE = os.path.dirname(os.path.abspath(__file__))
BOS, BYTE_OFFSET = 1, 3     # the served byte tokenizer: BOS, then byte + 3


def tolerance() -> Dict[str, Any]:
    with open(os.path.join(HERE, "tolerance.json")) as f:
        return json.load(f)


def byte_ids(text: str) -> List[int]:
    return [BOS] + [BYTE_OFFSET + b for b in text.encode("utf-8")]


def reference_logprobs(params: Dict[str, Any], model: Dict[str, Any],
                       ids: List[int], rows: List[int], pad_to: int,
                       variant: str = "") -> np.ndarray:
    """Log-probabilities ``[len(rows), vocab]`` after each of the positions
    ``rows`` of the sequence ``ids``. ``params`` is the engine's tree
    (``embed``, ``layer_<i>`` with ``attn``/``mlp``/norms, ``final_norm``,
    ``lm_head``). The sequence is padded at its END to ``pad_to`` so that one
    compiled layer serves every prompt; causality keeps the padding out of
    every real position."""
    seq = np.zeros((pad_to,), np.int32)
    seq[:len(ids)] = ids
    x = jnp.take(params["embed"]["embedding"], jnp.asarray(seq), axis=0
                 ).astype(jnp.float32)
    for i in range(model["num_hidden_layers"]):
        lp = params[f"layer_{i}"]
        w = {"q": lp["attn"]["q"], "k": lp["attn"]["k"], "v": lp["attn"]["v"],
             "o": lp["attn"]["o"], "gate": lp["mlp"]["gate"],
             "up": lp["mlp"]["up"], "down": lp["mlp"]["down"],
             "attn_norm": lp["attn_norm"]["scale"],
             "mlp_norm": lp["mlp_norm"]["scale"]}
        x = mistral.layer(
            x, w, n_heads=model["num_attention_heads"],
            n_kv=model["num_key_value_heads"], eps=model["rms_norm_eps"],
            theta=float(model["rope_theta"]), variant=variant)
    head = params["lm_head"] if "lm_head" in params else {
        "kernel": params["embed"]["embedding"].T}
    out = mistral.log_probs(x[jnp.asarray(rows)],
                            params["final_norm"]["scale"], head,
                            eps=model["rms_norm_eps"])
    return np.asarray(out)


def compare(entries: List[Dict[str, Any]], ref: np.ndarray
            ) -> Dict[str, float]:
    """The engine's per-token entries (``token``, ``logprob``, ``top_ids``,
    ``top_logprobs``) against the reference's rows, one row per entry."""
    worst, worst_margin_flip = 0.0, 0.0
    finite, total, n = True, 0.0, 0
    for e, row in zip(entries, ref):
        pairs = list(zip(e["top_ids"], e["top_logprobs"]))
        pairs.append((e["token"], e["logprob"]))
        for tid, lp in pairs:
            finite = finite and math.isfinite(lp)
            worst = max(worst, abs(lp - float(row[tid])))
            total, n = total + abs(lp - float(row[tid])), n + 1
        # the engine's greedy token against the reference's own best: where
        # they differ, the reference's margin between them must be small
        best = int(np.argmax(row))
        if best != e["token"]:
            worst_margin_flip = max(
                worst_margin_flip, float(row[best] - row[e["token"]]))
    return {"max_abs_logprob_diff": worst, "sum_abs_logprob_diff": total,
            "compared": n,
            "max_margin_of_a_flipped_top1": worst_margin_flip,
            "finite": finite}


def run(generate: Callable[[str, int], Dict[str, Any]],
        params: Dict[str, Any], model: Dict[str, Any],
        prompt_lengths: List[int], n_new: int, seed: int, pad_to: int,
        variant: str = "") -> Dict[str, Any]:
    """The whole check. ``generate(prompt, n_new)`` is the served path: it
    returns the engine's ``/generate`` answer with ``logprobs`` entries."""
    from ..traffic import prompt_text
    import random

    t0 = time.monotonic()
    tol = tolerance()
    rng = random.Random(int(seed) ^ 0xC0FFEE)
    worst = {"max_abs_logprob_diff": 0.0, "max_margin_of_a_flipped_top1": 0.0,
             "finite": True}
    n_positions, total, compared = 0, 0.0, 0
    for j, n_prompt in enumerate(prompt_lengths):
        text = prompt_text(n_prompt, f"ref{seed}p{j}", rng)
        out = generate(text, n_new)
        entries = out["logprobs"]
        ids = byte_ids(text)
        if out["n_prompt"] != len(ids):
            raise RuntimeError(f"the engine counted {out['n_prompt']} prompt "
                               f"tokens, the benchmark {len(ids)}")
        gen = [e["token"] for e in entries]
        seq = ids + gen[:-1]          # the last token is never fed back
        rows = [len(ids) - 1 + k for k in range(len(gen))]
        ref = reference_logprobs(params, model, seq, rows, pad_to, variant)
        got = compare(entries, ref)
        n_positions += len(gen)
        total, compared = (total + got["sum_abs_logprob_diff"],
                           compared + got["compared"])
        for k in ("max_abs_logprob_diff", "max_margin_of_a_flipped_top1"):
            worst[k] = max(worst[k], got[k])
        worst["finite"] = worst["finite"] and got["finite"]
    worst["mean_abs_logprob_diff"] = total / max(1, compared)
    passed = (worst["finite"] and n_positions > 0
              and worst["max_abs_logprob_diff"] <= tol["max_abs_logprob_diff"]
              and worst["mean_abs_logprob_diff"]
              <= tol["mean_abs_logprob_diff"]
              and worst["max_margin_of_a_flipped_top1"]
              <= tol["top1_must_match_above_margin"])
    return {**worst, "positions": n_positions, "passed": bool(passed),
            "tolerance": tol["max_abs_logprob_diff"],
            "seconds": time.monotonic() - t0}
