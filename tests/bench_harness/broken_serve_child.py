"""The serve child with the timed path broken underneath: every greedy
token is altered where it is produced. Requests still end 200 with every
token they asked for."""

import jax.numpy as jnp

from skypilot_tpu.infer import sampling

sampling.argmax_tokens = lambda logits: (
    (jnp.argmax(logits, axis=-1) + 1) % logits.shape[-1]).astype(jnp.int32)

from benchmarks.children import serve_child   # noqa: E402

serve_child.main()
