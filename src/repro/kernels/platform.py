"""The one platform decision behind every Pallas kernel of the repo.

``use_kernel=None`` means "auto" everywhere: the kernels are compiled for
the TPU and run there; elsewhere the reference jnp path runs. A kernel
forced on off-TPU (tests, ``--use-kernel``) runs in Pallas interpret mode,
so ``interpret`` is read from the backend and never defaulted.
"""
from __future__ import annotations

import jax


def on_tpu():
    return jax.default_backend() == "tpu"


def auto_use_kernel(flag):
    """Resolve ``use_kernel``: None -> on exactly when the default backend
    is TPU; True/False force the choice."""
    if flag is None:
        return on_tpu()
    return bool(flag)


def interpret():
    """Pallas interpret mode for a kernel that runs off-TPU; compiled on
    the TPU."""
    return not on_tpu()
