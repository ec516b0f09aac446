"""Which model file a serving configuration belongs to.

``--config <name>`` names a configuration of ANY decoder family the
serve path runs; the family's model module brings ``CONFIGS``,
``init_params`` and ``param_logical_axes``. The dictionaries are read
at call time, so a configuration registered after import (the
benchmark's families do that) is found.
"""

from __future__ import annotations

from typing import Any, Dict

from skypilot_tpu.models import (afmoe, glm_moe, lfm2_moe, llama,
                                 olmo_hybrid)

# Served decoder families, in lookup order. Each module brings, besides
# the three names above, ``SERVE_PROGRAMS``: the module that holds its
# serve programs (``infer.kvcache.programs_for``).
FAMILIES = (llama, glm_moe, olmo_hybrid, afmoe, lfm2_moe)


def serving_configs() -> Dict[str, Any]:
    out: Dict[str, Any] = {}
    for module in reversed(FAMILIES):
        out.update(module.CONFIGS)
    return out


def get_config(name: str):
    for module in FAMILIES:
        if name in module.CONFIGS:
            return module.CONFIGS[name]
    raise KeyError(
        f"unknown serving config {name!r}; known: "
        f"{sorted(serving_configs())}")


def model_for(cfg):
    """The model module of a config object: the family whose
    ``CONFIGS`` hold objects of its class (``llama`` for the subclasses
    of its config that other model files define)."""
    for module in FAMILIES[1:]:
        if type(cfg) in {type(c) for c in module.CONFIGS.values()}:
            return module
    return llama
