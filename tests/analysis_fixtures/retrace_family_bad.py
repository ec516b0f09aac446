# Golden fixture: seeded retrace-safety violations in the
# program-family shape — the engine's jitted entry points reach a
# model family's serve programs through a HANDLE
# (``progs = programs_for(cfg)``), not a module alias, and the latent
# cache / dropless expert layer invite their own mistakes: counting an
# expert's tokens on the host, sizing a group from traced counts.
# Checked as if it lived at skypilot_tpu/infer/. Never imported.
import jax
import jax.numpy as jnp
import numpy as np


def programs_for(cfg):
    return None


def experts_grouped(h, idx, n_experts):
    sizes = jnp.zeros((n_experts,), jnp.int32).at[idx].add(1)
    biggest = int(jnp.max(sizes))                 # expect: concretize
    if (sizes == 0).any():                        # expect: traced-branch
        biggest = biggest + 1
    rows = jnp.arange(jnp.max(sizes))             # expect: dynamic-shape
    return h[:biggest], rows


def decode_step(params, cache, table):
    lengths = np.asarray(cache["length"])         # expect: host-transfer
    rows = cache["c_kv"][0][table]
    return experts_grouped(rows, params["idx"], 8), lengths


def build(cfg):
    progs = programs_for(cfg)

    @jax.jit
    def _decode(params, cache, table):
        return progs.decode_step(params, cache, table)

    return _decode
