"""Production mesh builders (functions, not module-level constants, so that
importing this module never touches jax device state)."""
from __future__ import annotations

from repro.core.engine_dist import make_auto_mesh


def make_production_mesh(*, multi_pod: bool = False):
    """TPU v5e: 16x16 = 256 chips per pod; 2 pods = 512 chips multi-pod.

    The multi-pod mesh carries the same ``("pod", "data")`` leading axes
    as the CPU-testable collector mesh (``engine_dist.make_data_mesh(...,
    pods=...)`` / ``launch.multihost.make_pod_mesh``): the collector
    shards the pooled batch over ``collector_axis(mesh)`` — the pod-major
    name tuple — so an epoch validated on the multi-process CPU harness
    (tests/test_multihost.py) runs the identical collective schedule
    here."""
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return make_auto_mesh(shape, axes)


def make_host_mesh():
    """Single-device mesh for smoke tests / examples on CPU."""
    return make_auto_mesh((1, 1), ("data", "model"))
