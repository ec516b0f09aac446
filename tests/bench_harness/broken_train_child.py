"""The training child with the timed path broken underneath: the step
computes its loss and returns its state unchanged."""

import jax
import jax.numpy as jnp

from skypilot_tpu.train import qlora

_make = qlora.make_qlora_train_step


def _make_stuck(cfg, lc, tc):
    step = _make(cfg, lc, tc)

    def stuck(state, qweights, fp_params, batch):
        _, metrics = step(jax.tree.map(jnp.copy, state), qweights,
                          fp_params, batch)
        return state, metrics

    return stuck


qlora.make_qlora_train_step = _make_stuck

from benchmarks.children import train_child   # noqa: E402

train_child.main()
