"""Device mesh construction + multi-host initialization.

The reference has no distributed story at all — its "communication
backend" is BPF maps across the kernel/user boundary (SURVEY.md §5.8).
The TPU rebuild's scale-out axis is a ``jax.sharding.Mesh``: per-IP
state shards across devices by IP hash (collectives ride ICI), and the
classifier runs data-parallel over the batch on the same axis.  Beyond
one host, :func:`init_distributed` brings up JAX's multi-host runtime
(ICI within a slice, DCN across slices) — same code, bigger mesh.
"""

from __future__ import annotations

import jax
import numpy as np
from jax.sharding import Mesh


def make_mesh(
    n_devices: int | None = None, axis_name: str = "ip"
) -> Mesh:
    """1-D mesh over the first ``n_devices`` devices.

    The row-sharded IP table requires a power-of-two device count (slot
    ownership is computed from hash bits); enforce it here rather than
    failing obscurely inside the sharded step.
    """
    devs = jax.devices()
    n = n_devices if n_devices is not None else len(devs)
    if n > len(devs):
        raise ValueError(f"requested {n} devices, have {len(devs)}")
    if n & (n - 1):
        raise ValueError(f"device count must be a power of two, got {n}")
    return Mesh(np.asarray(devs[:n]), (axis_name,))


def init_distributed(
    coordinator_address: str | None = None,
    num_processes: int | None = None,
    process_id: int | None = None,
) -> None:
    """Initialize JAX's multi-host runtime (no-op on a single host).

    On TPU pods the arguments auto-populate from the environment;
    explicit values support manual bring-up.  After this,
    ``jax.devices()`` spans all hosts and :func:`make_mesh` builds a
    global mesh whose collectives ride ICI within a slice and DCN
    across slices.
    """
    if num_processes is not None and num_processes > 1 or coordinator_address:
        jax.distributed.initialize(
            coordinator_address=coordinator_address,
            num_processes=num_processes,
            process_id=process_id,
        )
