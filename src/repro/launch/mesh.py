"""Production and host mesh construction.

Defined as FUNCTIONS (never module-level constants) so importing this module
never touches jax device state — required because the dry-run must set
XLA_FLAGS before the first jax initialisation.
"""

from __future__ import annotations

import numpy as np

import jax


def make_production_mesh(*, multi_pod: bool = False):
    """TPU v5e production mesh: 16x16 = 256 chips/pod; 2 pods multi-pod.

    Axes: ``data`` (FSDP + batch), ``model`` (TP/EP), and ``pod`` (the
    cross-pod axis FedDD's sparse collectives compress) when multi_pod.
    """
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return jax.make_mesh(shape, axes,
                         axis_types=(jax.sharding.AxisType.Auto,) * len(axes))


def _largest_divisor_leq(n: int, k: int) -> int:
    """Largest divisor of ``n`` that is <= ``k`` (k >= 1)."""
    k = max(1, min(int(k), n))
    while n % k:
        k -= 1
    return k


def make_host_mesh(data: int = 1, model: int = 1):
    """Small 2-D mesh over whatever local devices exist (tests/examples).

    The requested axis sizes are clamped to DIVISORS of the available
    device count so the ``data * model`` product always tiles a prefix of
    ``jax.devices()`` exactly — asking for (data=3, model=1) on 8 devices
    yields a (2, 1) mesh rather than a shape-mismatch failure.
    """
    devs = jax.devices()
    n = len(devs)
    data = _largest_divisor_leq(n, data)
    model = _largest_divisor_leq(n // data, model)
    grid = np.asarray(devs[:data * model]).reshape(data, model)
    return jax.sharding.Mesh(grid, ("data", "model"))


def make_client_mesh(num_devices: int | None = None):
    """1-D ``clients`` mesh for the client-sharded round engines.

    Uses exactly ``num_devices`` local devices (all of them by default)
    and raises when fewer exist: a mesh quietly narrowed to the devices
    at hand would run, and pass, on a width nobody asked for.  This is
    the mesh :class:`repro.core.round_engine.ShardedRoundEngine` shards
    the fleet axis over; client counts need not divide the mesh — the
    engine zero-pads the trailing shard.
    """
    devs = jax.devices()
    k = len(devs) if num_devices is None else int(num_devices)
    if not 1 <= k <= len(devs):
        raise ValueError(
            f"a {k}-device clients mesh was asked for, but "
            f"{len(devs)} {devs[0].platform} device(s) are visible")
    return jax.sharding.Mesh(np.asarray(devs[:k]), ("clients",))


def resolve_client_mesh(mesh):
    """Normalise a ``ProtocolConfig.mesh`` value to a 1-D clients Mesh.

    Accepts an int (device count → :func:`make_client_mesh`, which
    raises when that many devices are not visible), ``True`` (all local
    devices), or an existing Mesh that carries a ``clients`` axis.
    """
    if mesh is True:
        return make_client_mesh()
    if isinstance(mesh, int):
        return make_client_mesh(mesh)
    if isinstance(mesh, jax.sharding.Mesh):
        if "clients" not in mesh.axis_names:
            raise ValueError(
                f"client-sharded engines need a 'clients' mesh axis; got "
                f"axes {mesh.axis_names}")
        return mesh
    raise TypeError(f"mesh must be an int, True, or jax.sharding.Mesh; "
                    f"got {type(mesh).__name__}")
