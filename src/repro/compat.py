"""Device-mesh helper shared by the sharded search paths.

The search service shards its slot pool over exactly one mesh axis
(``SearchService(mesh=)``, ``GoService(mesh=)``, ``Arena(mesh=)``);
:func:`make_service_mesh` builds that mesh.
"""
from __future__ import annotations

from typing import Any, Optional, Sequence

import jax
import numpy as np


def make_service_mesh(n_shard: int, axis: str = "shard",
                      devices: Optional[Sequence[Any]] = None):
    """A one-axis mesh over the first ``n_shard`` devices.

    ``jax.make_mesh`` always spans every device; the service wants a
    prefix of them (a one-shard mesh is the bit-identity control).
    """
    devices = list(jax.devices()) if devices is None else list(devices)
    if not 1 <= n_shard <= len(devices):
        raise ValueError(f"need 1 <= n_shard <= {len(devices)} available "
                         f"device(s), got {n_shard}")
    return jax.sharding.Mesh(np.asarray(devices[:n_shard]), (axis,))
