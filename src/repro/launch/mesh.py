"""Production mesh construction.

A FUNCTION (not a module-level constant) so importing this module never
touches jax device state — required because the dry-run sets
XLA_FLAGS=--xla_force_host_platform_device_count=512 before first jax init,
while tests/benches must see the single real CPU device.
"""
from __future__ import annotations

import jax


def make_production_mesh(*, multi_pod: bool = False):
    """Single pod: (data=16, model=16) = 256 chips (v5e pod).
    Multi-pod: (pod=2, data=16, model=16) = 512 chips; the 'pod' axis is pure
    data parallelism over DCI and composes with 'data' for gradient
    reductions (hierarchical: reduce-scatter intra-pod, all-reduce inter-pod).
    """
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return jax.make_mesh(shape, axes)


def make_host_mesh(model: int = 1):
    """Tiny mesh over however many devices exist — tests/examples."""
    n = len(jax.devices())
    model = min(model, n)
    return jax.make_mesh((n // model, model), ("data", "model"))
