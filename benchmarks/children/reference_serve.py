"""The plain reference over a sample of what a serve window produced.

Runs after the server has exited, in a process of its own (the chip
belongs to one process at a time, and ``memory_peak_bytes`` stays the
program's). Reads a sample file ``[{"prompt": [...], "tokens": [...]}]``,
runs the reference ONCE over each prompt with its served tokens, and
reports how far below the reference's best logit each served token lies.

With ``--control 1`` it also computes the control: the same reference in
the nearest precision below the stated one, put in the program's place —
at each position the token that lower precision puts first, and its gap.
"""

from __future__ import annotations

import argparse
import json
import sys
import time


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--config-file", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--sample", required=True)
    ap.add_argument("--chips", type=int, default=1)
    ap.add_argument("--control", type=int, default=0)
    ap.add_argument("--rehearse", type=int, default=0)
    args = ap.parse_args()
    t0 = time.time()
    with open(args.config_file) as f:
        config = json.load(f)
    with open(args.sample) as f:
        sample = json.load(f)

    import jax.numpy as jnp
    import numpy as np

    from benchmarks import manifest, weights
    from benchmarks.children import common
    from benchmarks.reference import decoder

    common.require_device(args.chips, bool(args.rehearse))
    cfg = manifest.model_dims(config)
    kind = "int8" if config["precision"]["weights"] == "int8" else "float"
    key = jnp.asarray(weights.seed_key(args.seed))

    pad_to = 16 if args.rehearse else 128
    longest = max(len(r["prompt"]) + len(r["tokens"]) - 1 for r in sample)
    S = -(-longest // pad_to) * pad_to
    tokens = np.zeros((len(sample), S), np.int32)
    rows, cols, served = [], [], []
    for i, r in enumerate(sample):
        seq = list(r["prompt"]) + list(r["tokens"][:-1])
        tokens[i, :len(seq)] = seq
        p = len(r["prompt"])
        for j, tok in enumerate(r["tokens"]):
            rows.append(i)
            cols.append(p - 1 + j)
            served.append(tok)
    rows, cols = np.asarray(rows), np.asarray(cols)
    served = np.asarray(served)

    stated = decoder.stated_precision(config)
    ref = decoder.Reference(cfg, kind, stated)
    logits = np.asarray(ref.logits_at(key, jnp.asarray(tokens), rows, cols))
    best = logits.max(axis=-1)
    gap = best - logits[np.arange(len(served)), served]
    out = {
        "positions": int(len(served)),
        "requests": len(sample),
        "padded_length": int(S),
        "served_gap_max": float(gap.max()),
        "served_gap_mean": float(gap.mean()),
        "served_not_argmax": int((gap > 0).sum()),
        "logit_std": float(logits.std()),
        "top2_gap_median": float(np.median(
            best - np.partition(logits, -2, axis=-1)[:, -2])),
        "precision": str(stated),
    }
    if args.control:
        # The contract's control, and the mildest lowering where it is
        # another precision (int8 weight data left as given).
        precisions = {"control": decoder.control_precision(config)}
        mild = decoder.control_precision(config, mild=True)
        if mild != precisions["control"]:
            precisions["control_mild"] = mild
        for label, low in precisions.items():
            if not (low.below(stated) or low.weight_bits):
                sys.exit("the control's precision is not below the stated")
            low_logits = np.asarray(
                decoder.Reference(cfg, kind, low).logits_at(
                    key, jnp.asarray(tokens), rows, cols))
            pick = low_logits.argmax(axis=-1)
            cgap = best - logits[np.arange(len(pick)), pick]
            out.update({f"{label}_gap_max": float(cgap.max()),
                        f"{label}_gap_mean": float(cgap.mean()),
                        f"{label}_not_argmax": int((cgap > 0).sum()),
                        f"{label}_precision": str(low)})
    out["seconds"] = time.time() - t0
    sys.stdout.write("BENCH_REFERENCE " + json.dumps(out) + "\n")
    sys.stdout.flush()


if __name__ == "__main__":
    main()
