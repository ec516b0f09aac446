"""jax.distributed bootstrap from the runtime env contract.

The gang driver (runtime/driver.py) injects JAX_COORDINATOR_ADDRESS /
JAX_NUM_PROCESSES / JAX_PROCESS_ID on every host, plus MEGASCALE_* on
multislice clusters (runtime/constants.py). jax reads only the
coordinator address natively, so user programs call this helper to join
the cluster-wide rendezvous with zero arguments.

Reference parity: the reference's contract is torchrun-shaped env vars
consumed by the user's launcher (sky/skylet/constants.py:319-322);
here the contract is jax-native and this helper is the launcher.
"""

from __future__ import annotations

import dataclasses
import os
from typing import Optional


@dataclasses.dataclass(frozen=True)
class ProcessTopology:
    num_processes: int
    process_id: int
    num_slices: int
    slice_id: int
    coordinator: Optional[str]


def topology_from_env() -> ProcessTopology:
    coord = os.environ.get("JAX_COORDINATOR_ADDRESS")
    num = int(os.environ.get("JAX_NUM_PROCESSES")
              or os.environ.get("SKYTPU_NUM_HOSTS") or "1")
    pid = int(os.environ.get("JAX_PROCESS_ID")
              or os.environ.get("SKYTPU_HOST_ID") or "0")
    n_slices = int(os.environ.get("MEGASCALE_NUM_SLICES") or "1")
    slice_id = int(os.environ.get("MEGASCALE_SLICE_ID") or "0")
    return ProcessTopology(num, pid, n_slices, slice_id, coord)


def initialize_from_env() -> ProcessTopology:
    """Join the cluster-wide jax.distributed rendezvous using only the
    injected env. No-op for single-process jobs. Idempotent."""
    topo = topology_from_env()
    if topo.num_processes > 1 and topo.coordinator:
        import jax
        if not jax.distributed.is_initialized():
            jax.distributed.initialize(
                coordinator_address=topo.coordinator,
                num_processes=topo.num_processes,
                process_id=topo.process_id)
    return topo
