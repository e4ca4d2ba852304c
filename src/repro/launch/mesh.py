"""Production mesh construction (TPU v5e pods).

A FUNCTION, not a module-level constant — importing this module never
touches jax device state (the dry-run sets XLA_FLAGS before first
init; tests and benches must keep seeing 1 CPU device).
"""
from __future__ import annotations

import jax
from jax.sharding import AxisType


def _auto_mesh(shape, axes):
    """jax.make_mesh with every axis Auto (the compiler partitions);
    jax.make_mesh alone defaults to Explicit axes."""
    return jax.make_mesh(shape, axes, axis_types=(AxisType.Auto,) * len(axes))


def make_production_mesh(*, multi_pod: bool = False):
    """16x16 = 256 chips/pod; 2 pods = 512 chips when multi_pod."""
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return _auto_mesh(shape, axes)


def make_host_mesh():
    """1-device mesh for CPU smoke runs of the pjit code paths."""
    return _auto_mesh((1, 1), ("data", "model"))


def make_federated_mesh(clients: int = 1):
    """1-D mesh whose single ``clients`` axis shards the federated
    round's client dimension (see ``core.fedavg.ClientSharding``): each
    of the ``clients`` devices owns K/clients participants of a round.
    On CPU, smoke-test multi-shard rounds with
    ``XLA_FLAGS=--xla_force_host_platform_device_count=8`` (must be set
    before jax first initializes)."""
    if clients < 1:
        raise ValueError(f"mesh needs >= 1 client shard, got {clients}")
    avail = jax.device_count()
    if clients > avail:
        raise ValueError(
            f"make_federated_mesh({clients}) needs {clients} devices but "
            f"only {avail} are visible — on CPU, export "
            f"XLA_FLAGS=--xla_force_host_platform_device_count={clients} "
            "before jax initializes"
        )
    return _auto_mesh((clients,), ("clients",))


# TPU v5e hardware constants for the roofline (per chip).
PEAK_FLOPS_BF16 = 197e12        # FLOP/s
HBM_BW = 819e9                  # B/s
ICI_BW = 50e9                   # B/s per link
