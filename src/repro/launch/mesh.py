"""Production mesh construction.

A FUNCTION (not a module-level constant) so importing this module never
touches jax device state — the dry-run must set XLA_FLAGS before first init.
"""

from __future__ import annotations

import jax

__all__ = ["make_production_mesh", "make_local_mesh"]


def _auto(n: int) -> tuple:
    """Auto axes: the runtime places arrays with ``with_sharding_constraint``,
    which refuses the Explicit axes ``jax.make_mesh`` makes by default."""
    return (jax.sharding.AxisType.Auto,) * n


def make_production_mesh(*, multi_pod: bool = False):
    """16x16 = 256 chips/pod ('data','model'); 2 pods = 512 chips multi-pod."""
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return jax.make_mesh(shape, axes, axis_types=_auto(len(axes)))


def make_local_mesh(model_parallel: int | None = None):
    """Mesh over whatever devices exist (tests / examples on CPU)."""
    n = len(jax.devices())
    mp = model_parallel or 1
    while n % mp:
        mp //= 2
    return jax.make_mesh((n // mp, mp), ("data", "model"), axis_types=_auto(2))


def make_fabric_aware_mesh(fabric, pods: int, per_pod_shape=(16, 16)):
    """Multi-pod mesh whose pod axis follows the fabric's ring embedding.

    Cross-pod ring collectives step between mesh-adjacent pods; ordering the
    pod axis by the Jellyfish ring embedding makes those steps land on the
    planned low-congestion physical routes (otherwise pod order is arbitrary
    and every hop crosses the fabric at random).  Returns (mesh, pod_order).
    """
    import numpy as np

    emb = fabric.ring(members=np.arange(pods))
    order = [int(p) for p in emb.order]
    devs = np.asarray(jax.devices())
    per_pod = per_pod_shape[0] * per_pod_shape[1]
    if len(devs) < pods * per_pod:
        raise ValueError(
            f"need {pods * per_pod} devices for {pods} pods, have {len(devs)}"
        )
    blocks = [devs[p * per_pod : (p + 1) * per_pod] for p in order]
    arr = np.stack(blocks).reshape((pods,) + tuple(per_pod_shape))
    from jax.sharding import Mesh

    return Mesh(arr, ("pod", "data", "model")), order
