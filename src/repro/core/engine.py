"""Round engines for the three DCML schemes: SFPL (ours/paper), SFLv2, FL.

Simulation semantics (single host, jit-compiled):
  * clients are a stacked leading axis N on the client-side param/state trees
  * SFPL: per local-batch step, all clients forward in parallel (vmap), the
    GlobalCollector pools + shuffles smashed data, ONE server-side update
    runs on the pooled shuffled stack, per-sample activation gradients are
    de-shuffled and routed back, clients update locally (vmap). At epoch end
    ClientFedServer averages client models EXCLUDING BatchNorm.
  * SFLv2: clients are visited sequentially in random order; the single
    server-side model trains on each client's (single-class) stream in turn
    — this sequential structure is the catastrophic-forgetting mechanism
    under study and must not be parallelized. Epoch end: FedAvg including BN
    (paper's RMSD setup).
  * FL: every client trains the full model locally; FedAvg everything.

The engine is generic over a ``SplitModel`` (client_fwd / server_loss /
full_loss closures) so the same machinery drives ResNets (paper) and the
cut-transformer LM variants.

The scheme step bodies live in ``repro.core.round`` — ONE placement-
agnostic implementation parameterized by collector strategy and placement
objects. ``sfpl_epoch`` / ``sflv2_epoch`` here are the single-device
entrypoints (thin wrappers pinning the historical signatures and
numerics); ``engine_dist`` wraps the same bodies for the mesh.
"""
from __future__ import annotations

import dataclasses
from typing import Callable

import jax
import jax.numpy as jnp

from repro.core import collector as C
from repro.core import round as RD
from repro.core.bn_policy import fedavg, aggregate_bn_state
from repro.core.round import make_client_update  # noqa: F401  (re-export)
from repro.models.common import IGNORE_LABEL, softmax_cross_entropy


@dataclasses.dataclass(frozen=True)
class SplitModel:
    # (cparams, cstate, x, training, rmsd) -> (smashed, new_cstate)
    client_fwd: Callable
    # (sparams, sstate, A, y, training, rmsd[, valid]) ->
    #     (loss, (new_sstate, logits)); the keyword-only ``valid`` row mask
    #     is required only when the engine runs with elastic participation
    server_loss: Callable
    # (params, state, x, y, training, rmsd) -> (loss, (new_state, logits))
    full_loss: Callable


def make_resnet_split(cfg, policy=None):
    """SplitModel closures for the paper's ResNet-8/32/56.

    ``policy`` (a ``models.common.ComputePolicy``) selects the
    mixed-precision compute path: master params stay f32 (autodiff through
    the in-loss cast delivers f32 grads), convs and the BN+ReLU epilogues
    run in ``policy.compute_dtype``, the smashed data crosses the collector
    in that dtype, and the loss reduces in f32 — via the fused Pallas
    ``softmax_xent`` when ``policy.fused()``.  ``None`` keeps the original
    f32 graph bit-for-bit."""
    from repro.models import resnet as R

    if policy is None:
        loss_fn = softmax_cross_entropy
    elif policy.fused():
        from repro.kernels.platform import interpret
        from repro.kernels.softmax_xent import ops as _xent
        def loss_fn(logits, y):
            return _xent.softmax_xent(logits, y, interpret=interpret())
    else:
        loss_fn = softmax_cross_entropy

    def client_fwd(cp, cs, x, training=True, rmsd=None):
        return R.client_apply(cp, cs, x, training=training, rmsd=rmsd,
                              policy=policy)

    def server_loss(sp, ss, a, y, training=True, rmsd=None, valid=None):
        if valid is not None:
            # Elastic participation: absent clients' rows ride along for
            # static shapes but must be inert — zero their activations
            # (exact zero grads through jnp.where), drop their labels to
            # IGNORE_LABEL (the loss already means over valid rows), and
            # exclude them from every BN batch statistic.
            vb = valid.reshape((-1,) + (1,) * (a.ndim - 1))
            a = jnp.where(vb, a, jnp.zeros((), a.dtype))
            y = jnp.where(valid, y, IGNORE_LABEL)
        logits, nss = R.server_apply(sp, ss, a, cfg, training=training,
                                     rmsd=rmsd, policy=policy, valid=valid)
        return loss_fn(logits, y), (nss, logits)

    def full_loss(p, s, x, y, training=True, rmsd=None):
        logits, ns = R.apply(p, s, x, cfg, training=training, rmsd=rmsd,
                             policy=policy)
        return loss_fn(logits, y), (ns, logits)

    return SplitModel(client_fwd, server_loss, full_loss)


# --------------------------------------------------------------------------
# state containers

def init_dcml_state(key, init_fn, num_clients, opt_client, opt_server):
    """init_fn(key) -> ({"client":..., "server":...} params, state)."""
    params, state = init_fn(key)
    rep = lambda t: jax.tree_util.tree_map(
        lambda a: jnp.broadcast_to(a[None], (num_clients,) + a.shape).copy(),
        t)
    return {
        "cp": rep(params["client"]),
        "cbn": rep(state["client"]),
        "sp": params["server"],
        "sbn": state["server"],
        "copt": rep(opt_client.init(params["client"])),
        "sopt": opt_server.init(params["server"]),
        "step": jnp.zeros((), jnp.int32),
    }


# --------------------------------------------------------------------------
# SFPL epoch (Algorithm 1 + 2)

def sfpl_epoch(key, st, data, split: SplitModel, opt_c, opt_s, *,
               num_clients, batch_size, bn_mode="cmsd", alpha=1.0,
               participation=None):
    """data: {"x": (N, n, ...), "y": (N, n)}. One epoch = scan over the
    n // batch_size local batches — ``round.sfpl_round`` with the dense
    single-device collector.

    ``bn_mode`` selects the paper's two SFPL aggregation variants:
      * "cmsd" — ClientFedServer EXCLUDES BatchNorm (params + stats stay
        local); inference uses current-batch statistics. Wins for non-IID
        testing (Table VIII).
      * "rmsd" — BatchNorm params and running stats ARE aggregated;
        inference uses the aggregated running statistics. Wins for IID
        testing (Tables VI, VII).

    ``participation`` (optional ``(num_clients,)`` or ``(steps,
    num_clients)`` bool) masks absent clients for the epoch or per step —
    see :func:`repro.core.round.sfpl_round`.
    """
    return RD.sfpl_round(
        key, st, data, split, opt_c, opt_s, num_clients=num_clients,
        batch_size=batch_size, bn_mode=bn_mode,
        collector=RD.SINGLE.collector(num_clients, alpha=alpha),
        participation=participation)


# --------------------------------------------------------------------------
# SFLv2 epoch (baseline under study)

def sflv2_epoch(key, st, data, split: SplitModel, opt_c, opt_s, *,
                num_clients, batch_size, aggregate_bn=True):
    return RD.sflv2_round(
        key, st, data, split, opt_c, opt_s, num_clients=num_clients,
        batch_size=batch_size, aggregate_bn=aggregate_bn,
        placement=RD.SINGLE)


def jit_epoch(epoch, data, *, num_clients, alpha=1.0):
    """Jitted hot loop around ``epoch(key, st, data[, participation]) ->
    (st, losses)``: returns ``run(key, st[, participation])`` with ``data``
    bound and the carried state donated, so the param/opt buffers are
    reused in place.

    ``data`` rides through the jit boundary as an ARGUMENT, not a closure:
    multi-host global arrays span non-addressable devices and jax refuses
    to close over them. ``run.jitted`` is the jitted ``(key, st, data[,
    participation])`` function itself, for ``.lower()``.

    A ``participation`` mask (``(num_clients,)`` or ``(steps,
    num_clients)`` bool) is validated eagerly on the host (>= 1 survivor
    per flush group of ``alpha``) and then rides through the jit boundary
    as a TRACED argument: every epoch's mask reuses one specialization
    instead of retracing per draw of a fault schedule. ``None`` and masked
    epochs are separate specializations (two traces)."""
    jitted = jax.jit(epoch, donate_argnums=(1,))

    def run(key, st, participation=None):
        # the dispatch on the profiler's host clock, beside the device ops
        with jax.profiler.TraceAnnotation("sfpl.epoch"):
            if participation is None:
                return jitted(key, st, data)
            mask = C.check_participation(num_clients, participation,
                                         alpha=alpha)
            return jitted(key, st, data, jnp.asarray(mask))
    run.jitted = jitted
    return run


# --------------------------------------------------------------------------
# FL (FedAvg) epoch

def fl_epoch(key, st, data, split: SplitModel, opt_full, *,
             num_clients, batch_size, aggregate_bn=True):
    """st here holds full-model copies per client:
    {"p": (N, ...), "bn": (N, ...), "opt": (N, ...), "step"}."""
    del key
    n_local = data["x"].shape[1]
    steps = n_local // batch_size

    def per_client(p, bn, opt, xk, yk, step0):
        def per_batch(inner, idx):
            p, bn, opt, step = inner
            xb = jax.lax.dynamic_slice_in_dim(xk, idx * batch_size,
                                              batch_size, axis=0)
            yb = jax.lax.dynamic_slice_in_dim(yk, idx * batch_size,
                                              batch_size, axis=0)

            def loss_fn(p_):
                loss, (ns, _) = split.full_loss(p_, bn, xb, yb, True, None)
                return loss, ns
            (loss, nbn), g = jax.value_and_grad(
                loss_fn, has_aux=True)(p)
            p_new, opt_new = opt_full.update(g, opt, p, step)
            return (p_new, nbn, opt_new, step + 1), loss

        (p, bn, opt, _), losses = jax.lax.scan(
            per_batch, (p, bn, opt, step0), jnp.arange(steps))
        return p, bn, opt, losses

    p, bn, opt, losses = jax.vmap(
        per_client, in_axes=(0, 0, 0, 0, 0, None))(
        st["p"], st["bn"], st["opt"], data["x"], data["y"], st["step"])
    p = fedavg(p, exclude_bn=False)
    bn = aggregate_bn_state(bn, aggregate=aggregate_bn)
    return dict(st, p=p, bn=bn, opt=opt, step=st["step"] + steps), losses


def init_fl_state(key, init_fn, num_clients, opt_full):
    params, state = init_fn(key)
    full_p = {"client": params["client"], "server": params["server"]}
    full_s = {"client": state["client"], "server": state["server"]}
    rep = lambda t: jax.tree_util.tree_map(
        lambda a: jnp.broadcast_to(a[None], (num_clients,) + a.shape).copy(),
        t)
    return {"p": rep(full_p), "bn": rep(full_s),
            "opt": rep(opt_full.init(full_p)),
            "step": jnp.zeros((), jnp.int32)}
