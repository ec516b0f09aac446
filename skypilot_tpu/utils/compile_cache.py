"""One place that decides where JAX's persistent compilation cache lives.

Every process that compiles for the device calls :func:`configure`
first thing — the server, the trainer, ``train.evaluate``, the benches,
the test suite, and ``chip_smoke.py`` on behalf of its children — so a
second process (or a second run in the same checkout) finds what the
first one compiled instead of paying an 8B compile again.

The rule: if ``JAX_COMPILATION_CACHE_DIR`` is set, it wins and nothing
here touches it (JAX reads the variable itself). Otherwise the cache is
``<checkout>/.jax_cache`` — a FIXED path, because the directory is part
of how a run finds its cache again: a path built from a temp dir, a pid
or the time never hits.

Stdlib only, and it never imports JAX: ``chip_smoke.py``'s parent must
stay off the chip.
"""

from __future__ import annotations

import os
import sys

ENV_VAR = "JAX_COMPILATION_CACHE_DIR"
# Cache everything that took a noticeable compile: the defaults (1 s,
# 0 bytes is already the size default) skip the many sub-second
# programs a serving engine or the CPU test suite compiles.
_THRESHOLDS = {
    "JAX_PERSISTENT_CACHE_MIN_COMPILE_TIME_SECS": 0.2,
    "JAX_PERSISTENT_CACHE_MIN_ENTRY_SIZE_BYTES": 0,
}


def default_dir() -> str:
    """``<checkout>/.jax_cache`` (listed in .gitignore)."""
    root = os.path.dirname(os.path.dirname(os.path.dirname(
        os.path.abspath(__file__))))
    return os.path.join(root, ".jax_cache")


def configure() -> str:
    """Point this process and its children at the cache; return the
    directory in use. Works before or after ``import jax``: the
    variables are what children inherit, and an already-imported JAX
    (which read its defaults at import) is told through its config."""
    fresh = {}
    if not os.environ.get(ENV_VAR):
        fresh[ENV_VAR] = default_dir()
    for name, value in _THRESHOLDS.items():
        if name not in os.environ:
            fresh[name] = value
    os.environ.update({name: str(v) for name, v in fresh.items()})
    jax = sys.modules.get("jax")
    if jax is not None:
        for name, value in fresh.items():
            jax.config.update(name.lower(), value)
    return os.environ[ENV_VAR]


def is_warm(path: str) -> bool:
    """Whether ``path`` already holds at least one cached program."""
    try:
        return any(os.scandir(path))
    except OSError:
        return False
