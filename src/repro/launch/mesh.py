"""Production mesh construction.

``make_production_mesh`` is a function (never a module-level constant) so
importing this module touches no jax device state — required because the
dry-run must set XLA_FLAGS before any jax initialisation.
"""

from __future__ import annotations

import jax
from jax.sharding import AxisType


def _auto_mesh(shape: tuple, axes: tuple):
    """``jax.make_mesh`` with Auto axes: the round and serving engines place
    operands with ``NamedSharding``s and ``with_sharding_constraint`` and
    leave the rest to GSPMD, which Explicit axes (``make_mesh``'s default)
    refuse."""
    return jax.make_mesh(shape, axes, axis_types=(AxisType.Auto,) * len(axes))


def make_production_mesh(*, multi_pod: bool = False):
    """16×16 = 256 chips single pod; 2×16×16 = 512 chips across two pods."""
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return _auto_mesh(shape, axes)


def make_debug_mesh(n_data: int = 2, n_model: int = 2):
    """Small host-device mesh for tests (requires XLA host-device override)."""
    return _auto_mesh((n_data, n_model), ("data", "model"))


def make_round_mesh(n_client: int, n_model: int = 1):
    """Federated-round mesh for ``FederatedTrainer(mesh=...)``: sampled
    clients split over ``"client"`` (``n_client`` groups), each group's
    local training tensor-parallel over ``"model"`` (``n_model`` devices).
    ``n_model=1`` returns the 1-D client mesh (pure client parallelism —
    the ``shard_map`` path); needs ``n_client * n_model`` devices."""
    need = n_client * n_model
    have = len(jax.devices())
    if have < need:
        raise ValueError(
            f"make_round_mesh({n_client}, {n_model}) needs {need} devices, "
            f"have {have} (set XLA_FLAGS="
            f"--xla_force_host_platform_device_count={need} before jax "
            "initialises to force host devices)")
    if n_model == 1:
        import numpy as np
        return jax.sharding.Mesh(
            np.asarray(jax.devices()[:n_client]), ("client",))
    return _auto_mesh((n_client, n_model), ("client", "model"))
