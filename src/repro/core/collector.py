"""SFPL's global collector function (paper Algorithm 1).

The collector accumulates smashed data + labels from all clients, applies a
random shuffle before server-side training, and de-shuffles the returned
activation gradients so each slice is routed back to its source client.

Three implementations with identical semantics:
  * ``shuffle`` / ``deshuffle``           — jnp take (simulation default)
  * ``shuffle(..., use_kernel=True)``     — Pallas gather kernel
  * ``distributed_shuffle``               — mesh-aware: the pooled batch axis
    is sharded over ("pod","data"); a global permutation gather compiles to
    all-to-all / collective-permute on the data axis (the paper's
    "collect from all clients then scatter back" — without ever
    materializing the pool on one device).
"""
from __future__ import annotations

import jax
import jax.numpy as jnp


def make_permutation(key, n):
    return jax.random.permutation(key, n)


def inverse_permutation(perm):
    return jnp.argsort(perm)


def _permute_leaf(x, perm, use_kernel):
    if use_kernel:
        from repro.kernels.collector_permute.ops import collector_permute_ad
        from repro.kernels.platform import interpret
        return collector_permute_ad(x, perm, interpret())
    return jnp.take(x, perm, axis=0)


def shuffle(tree, perm, *, use_kernel=False):
    """Apply ``perm`` along axis 0 of every leaf (smashed data + labels).
    ``use_kernel`` runs the Pallas gather: compiled on TPU, in interpret
    mode elsewhere."""
    return jax.tree_util.tree_map(
        lambda x: _permute_leaf(x, perm, use_kernel), tree)


def deshuffle(tree, perm, *, use_kernel=False):
    """Inverse of ``shuffle`` — routes gradients back to source clients."""
    inv = inverse_permutation(perm)
    return jax.tree_util.tree_map(
        lambda x: _permute_leaf(x, inv, use_kernel), tree)


def collect(per_client_tree):
    """Stack per-client tensors (N, B, ...) into the pooled stack (N*B, ...).

    Mirrors the paper's ActivationStack/LabelStack keyed by client id: row
    ``k * B + j`` is sample j of client k, so ``uncollect`` can route
    results back deterministically.
    """
    return jax.tree_util.tree_map(
        lambda x: x.reshape((-1,) + x.shape[2:]), per_client_tree)


def uncollect(pooled_tree, num_clients):
    """Inverse of ``collect``: (N*B, ...) -> (N, B, ...)."""
    return jax.tree_util.tree_map(
        lambda x: x.reshape((num_clients, -1) + x.shape[1:]), pooled_tree)


def flush_group_sizes(num_clients, alpha):
    """Clients per collector flush under the paper's accumulation threshold:
    the collector flushes every ceil(alpha*N) client batches, so alpha=1 is
    one global flush and alpha=0.5 with N=10 gives two 5-client pools."""
    fc = max(1, min(num_clients, round(alpha * num_clients)))
    num_flushes = -(-num_clients // fc)
    return [min(fc, num_clients - f * fc) for f in range(num_flushes)]


def make_flush_perm(key, n, num_clients, alpha):
    """Pool permutation honouring the accumulation threshold: rows are
    shuffled within contiguous client-major flush groups, never across
    group boundaries. The canonical single-device collector permutation —
    the mesh strategies reproduce its group structure with balanced
    per-group exchanges (collector_dist.make_grouped_balanced_perm)."""
    groups = flush_group_sizes(num_clients, alpha)
    if len(groups) <= 1:
        return make_permutation(key, n)
    per_client = n // num_clients
    parts, start = [], 0
    for f, c in enumerate(groups):
        size = c * per_client
        sub = make_permutation(jax.random.fold_in(key, f), size)
        parts.append(sub + start)
        start += size
    return jnp.concatenate(parts)


def check_participation(num_clients, participation, *, alpha=1.0):
    """Validate an elastic-participation mask eagerly (host side).

    ``participation`` is a bool mask of shape ``(num_clients,)`` (static
    per-epoch) or ``(steps, num_clients)`` (per-step).  Every flush group
    must keep at least one surviving client — an all-absent group would
    leave its pooled slice with zero valid rows and the server update for
    that slice undefined.  Raises ``ValueError`` naming the offending
    flush group (and step, for per-step masks); returns the mask as a
    numpy bool array.

    >>> import numpy as np
    >>> check_participation(4, [True, False, True, True], alpha=0.5)
    array([ True, False,  True,  True])
    >>> check_participation(4, [True, True, False, False],
    ...                     alpha=0.5)  # doctest: +ELLIPSIS
    Traceback (most recent call last):
        ...
    ValueError: participation mask drops ALL clients of flush group 1 ...
    """
    import numpy as np
    if participation is None:
        return None
    mask = np.asarray(participation)
    if mask.ndim not in (1, 2) or mask.shape[-1] != num_clients:
        raise ValueError(
            f"participation mask must have shape ({num_clients},) or "
            f"(steps, {num_clients}); got {mask.shape}")
    mask = mask.astype(bool)
    groups = flush_group_sizes(num_clients, alpha)
    rows = mask[None] if mask.ndim == 1 else mask
    start = 0
    for g, c in enumerate(groups):
        alive = rows[:, start:start + c].any(axis=1)
        if not alive.all():
            step = int(np.argmin(alive))
            at = "" if mask.ndim == 1 else f" at step {step}"
            raise ValueError(
                f"participation mask drops ALL clients of flush group {g} "
                f"(clients {start}..{start + c}, alpha={alpha}){at} — "
                f"each flush group needs >= 1 surviving client")
        start += c
    return mask


def participation_row_mask(mask, batch_size):
    """Expand a per-client mask to the client-major pooled row mask:
    row ``k * batch_size + j`` is valid iff client ``k`` participates."""
    return jnp.repeat(jnp.asarray(mask, dtype=bool), batch_size)


def distributed_shuffle(x, perm):
    """Mesh-aware collector: ``x`` is the pooled global batch whose leading
    axis is sharded over ("pod","data")). A gather by a global permutation is
    SPMD-partitioned by XLA into all-to-all / collective-permute exchanges —
    the TPU-native form of the paper's collect-shuffle-scatter.

    Differentiable: the VJP of the gather is the de-shuffling scatter, so the
    returned-gradient routing of Algorithm 1 falls out of autodiff.
    """
    return jnp.take(x, perm, axis=0)


class GlobalCollector:
    """Stateful convenience wrapper for the simulation engine.

    ``alpha`` mirrors the paper's accumulation threshold (the collector waits
    for ``alpha * N`` client batches before shuffling). In the synchronous
    simulation every client contributes each round, so alpha scales how many
    pooled batches form one shuffle unit.
    """

    def __init__(self, num_clients, *, alpha=1.0, use_kernel=False):
        self.num_clients = num_clients
        self.alpha = alpha
        self.use_kernel = use_kernel

    def make_pool_perm(self, key, n):
        """Permutation honouring the paper's accumulation threshold (see
        ``make_flush_perm``): alpha=1 -> one global shuffle; alpha=0.5 with
        N=10 -> two independent 5-client pools."""
        return make_flush_perm(key, n, self.num_clients, self.alpha)

    def shuffle_pool(self, key, per_client_acts, per_client_labels):
        pooled = collect({"a": per_client_acts, "y": per_client_labels})
        n = pooled["a"].shape[0]
        perm = self.make_pool_perm(key, n)
        shuffled = shuffle(pooled, perm, use_kernel=self.use_kernel)
        return shuffled["a"], shuffled["y"], perm

    def deshuffle_grads(self, grads_pool, perm):
        d = deshuffle({"g": grads_pool}, perm, use_kernel=self.use_kernel)
        return uncollect(d, self.num_clients)["g"]
