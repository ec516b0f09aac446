# Clean twin of retrace_family_bad.py: group sizes stay on the device
# and feed a ragged product, the cache's lengths mask instead of
# slicing, and nothing under the handle concretizes.
import jax
import jax.numpy as jnp
from jax import lax


def programs_for(cfg):
    return None


def experts_grouped(h, idx, w, n_experts):
    sizes = jnp.zeros((n_experts,), jnp.int32).at[idx].add(1)
    order = jnp.argsort(idx, stable=True)
    return lax.ragged_dot(h[order], w, sizes)


def decode_step(params, cache, table):
    rows = cache["c_kv"][0][table]
    valid = jnp.arange(rows.shape[1])[None, :] < cache["length"][:, None]
    rows = jnp.where(valid[..., None], rows, 0)
    return experts_grouped(rows[:, 0], params["idx"], params["w"], 8)


def build(cfg):
    progs = programs_for(cfg)

    @jax.jit
    def _decode(params, cache, table):
        return progs.decode_step(params, cache, table)

    return _decode
