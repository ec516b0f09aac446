"""Evaluation: loss / perplexity / accuracy over a batch stream.

``python -m skypilot_tpu.train.evaluate --ckpt-dir ... [--packed]``
restores the latest checkpoint and reports aggregate metrics — the
resume-side counterpart of train.run (reference analogue: eval steps
inside external workload recipes).
"""

from __future__ import annotations

import argparse
import json
import math
import sys
from typing import Callable, Dict, Iterable, Optional


def evaluate(step_less_loss_fn: Callable, params,
             batches: Iterable[Dict]) -> Dict[str, float]:
    """Aggregate token-weighted loss/accuracy over ``batches``.

    ``step_less_loss_fn(params, batch) -> (loss, metrics)`` is the
    model's loss_fn, already jitted/sharded by the caller.
    """
    total_loss = 0.0
    total_tokens = 0.0
    total_correct = 0.0
    n_batches = 0
    for batch in batches:
        loss, metrics = step_less_loss_fn(params, batch)
        tokens = float(metrics.get("tokens", 1.0))
        total_loss += float(loss) * tokens
        total_correct += float(metrics.get("accuracy", 0.0)) * tokens
        total_tokens += tokens
        n_batches += 1
    if total_tokens == 0:
        return {"loss": float("nan"), "perplexity": float("nan"),
                "accuracy": float("nan"), "tokens": 0, "batches": 0}
    loss = total_loss / total_tokens
    return {
        "loss": round(loss, 6),
        "perplexity": round(math.exp(min(loss, 30.0)), 4),
        "accuracy": round(total_correct / total_tokens, 6),
        "tokens": int(total_tokens),
        "batches": n_batches,
    }


def main() -> None:
    from skypilot_tpu.utils import compile_cache
    compile_cache.configure()
    ap = argparse.ArgumentParser()
    ap.add_argument("--model", default="llama", choices=("llama", "moe"))
    ap.add_argument("--config", default=None)
    ap.add_argument("--ckpt-dir", default=None,
                    help="restore params from the latest checkpoint")
    ap.add_argument("--batches", type=int, default=8)
    ap.add_argument("--batch", type=int, default=4)
    ap.add_argument("--seq", type=int, default=1024)
    ap.add_argument("--packed", action="store_true")
    ap.add_argument("--tp", type=int, default=1)
    args = ap.parse_args()

    import jax

    from skypilot_tpu.parallel import mesh as mesh_lib
    from skypilot_tpu.train import trainer

    if args.model == "llama":
        from skypilot_tpu.models import llama as model
        default_cfg = "llama3-400m"
    else:
        from skypilot_tpu.models import moe as model
        default_cfg = "moe-small"
    cfg = model.CONFIGS[args.config or default_cfg]
    args.seq = min(args.seq, cfg.max_seq_len)

    mesh = mesh_lib.make_mesh(
        mesh_lib.default_shape_for(jax.device_count(), tp=args.tp))
    tc = trainer.TrainConfig()

    if args.ckpt_dir:
        from skypilot_tpu.train import checkpoints
        mgr = checkpoints.CheckpointManager(args.ckpt_dir)
        target = trainer.create_abstract_state(cfg, tc, mesh, model=model)
        state = mgr.restore(target)
        params = state["params"]
        print(f"restored step {mgr.latest_step()}", file=sys.stderr)
    else:
        params = trainer.create_train_state(cfg, tc, mesh,
                                            model=model)["params"]

    from skypilot_tpu.parallel import sharding as sh
    constrain = sh.make_constrain(mesh, sh.ACT_RULES)
    loss_fn = jax.jit(
        lambda p, b: model.loss_fn(p, b, cfg, constrain, mesh))

    if args.packed:
        import jax.numpy as jnp

        from skypilot_tpu.data import input_pipeline as ip
        docs = ip.synthetic_doc_stream(
            args.batches * args.batch * 4, cfg.vocab_size,
            mean_len=args.seq // 3, seed=1)
        stream = ip.packed_batches(docs, args.batch, args.seq)
        batches = ({k: jnp.asarray(v) for k, v in b.items()}
                   for _, b in zip(range(args.batches), stream))
    else:
        batches = (trainer.synthetic_batch(cfg, args.batch, args.seq,
                                           seed=i)
                   for i in range(args.batches))

    out = evaluate(loss_fn, params, batches)
    print(json.dumps(out))


if __name__ == "__main__":
    main()
