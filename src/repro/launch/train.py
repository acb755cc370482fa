"""Training driver.

Two modes:
  * LM mode (default): train an assigned-architecture smoke config on the
    synthetic Markov token stream — runnable on CPU, demonstrates the full
    step (optimizer, schedule, checkpointing) and the SFPL collector option
    (``--sfpl`` inserts the cut-layer shuffle into the jitted step).
  * Paper mode (``--paper``): a DCML round engine on the synthetic
    CIFAR-like set with a split ResNet. ``--scheme`` picks SFPL (default)
    or the SFLv2 baseline; ``--sharded`` runs the same round body on a
    ("data",) mesh across all visible devices (SFPL: clients + pooled
    smashed batch sharded, collector as an explicit all_to_all in
    ``--collector {balanced,uniform}`` mode with flush threshold
    ``--alpha``; SFLv2: the server stream sharded over the batch axis).
    ``--pipeline double_buffered`` streams the collector: each flush
    group's exchange overlaps the next group's client forward (see
    docs/collector_modes.md); ``--submesh`` / ``--no-submesh`` force the
    streamed sub-mesh routing on/off (default: auto when the balanced
    grouped layout qualifies). The exchange's local bucket gathers run
    through the Pallas collector kernels automatically on TPU
    (``--use-kernel`` / ``--no-kernel`` force the choice). To simulate a
    mesh on CPU, set XLA_FLAGS=--xla_force_host_platform_device_count=8
    before launching.

Usage:
  PYTHONPATH=src python -m repro.launch.train --arch qwen3-8b --smoke \
      --steps 50 [--sfpl] [--ckpt out.npz]
  PYTHONPATH=src python -m repro.launch.train --paper --sharded \
      --clients 8 --epochs 4 [--scheme sflv2] [--alpha 0.5] \
      [--collector uniform] [--pipeline double_buffered] [--submesh] \
      [--use-kernel] [--wire-dtype int8] [--model resnet32] \
      [--ckpt state.npz --ckpt-every 1] [--resume state.npz] \
      [--drop-rate 0.2 --straggler-rate 0.1 --straggler-timeout 0.5]
"""
from __future__ import annotations

import argparse
import time
from typing import Callable, NamedTuple

import jax
import jax.numpy as jnp

from repro.configs import get_arch
from repro.data.tokens import synthetic_token_stream
from repro.launch.compile_cache import enable_compile_cache
from repro.launch.steps import make_train_step
from repro.optim import sgd_momentum, adamw, cosine_lr
from repro.checkpoint import save_checkpoint


def train_lm(arch_id, *, steps=50, batch=8, seq=64, smoke=True, sfpl=False,
             lr=3e-3, optimizer="adamw", ckpt=None, log_every=10):
    spec = get_arch(arch_id)
    cfg = (spec.make_smoke_config() if smoke else spec.make_config())
    model = spec.model
    key = jax.random.PRNGKey(0)
    params = model.init(key, cfg)

    opt = (adamw(cosine_lr(lr, steps)) if optimizer == "adamw"
           else sgd_momentum(cosine_lr(lr, steps), momentum=0.9))
    opt_state = opt.init(params)
    step_fn = jax.jit(make_train_step(spec, cfg, opt, sfpl=sfpl))

    vocab = cfg.vocab_size
    step = jnp.zeros((), jnp.int32)
    t0 = time.time()
    losses = []
    for i in range(steps):
        key, kd, kp = jax.random.split(key, 3)
        toks, labels = synthetic_token_stream(kd, batch=batch, seq_len=seq,
                                              vocab=vocab)
        batch_in = {"tokens": toks, "labels": labels}
        if spec.family == "whisper":
            batch_in["frame_embeds"] = jax.random.normal(
                kd, (batch, 16, cfg.d_model), jnp.float32)
        if getattr(cfg, "vision_tokens", 0):
            batch_in["vision_embeds"] = jax.random.normal(
                kd, (batch, cfg.vision_tokens, cfg.d_model))
        if sfpl:
            batch_in["perm"] = jax.random.permutation(kp, batch)
        params, opt_state, step, loss = step_fn(params, opt_state, step,
                                                batch_in)
        losses.append(float(loss))
        if i % log_every == 0 or i == steps - 1:
            print(f"step {i:4d} loss {float(loss):.4f} "
                  f"({time.time()-t0:.1f}s)", flush=True)
    if ckpt:
        save_checkpoint(ckpt, params, step=int(step))
        print(f"saved checkpoint to {ckpt}")
    return losses


def make_compute_policy(compute_dtype, use_kernel=None, wire_dtype=None,
                        wire_dtype_bwd=None):
    """``ComputePolicy`` for the launchers' ``--compute-dtype`` /
    ``--wire-dtype`` knobs, or ``None`` at the all-default configuration
    (f32 compute, identity wire — which keeps the original unfused graph
    bit-for-bit, the parity baseline). A narrow wire at f32 compute is a
    valid policy on its own: the model computes in f32 and only the
    exchange payload narrows. Off-TPU the fused kernels run in interpret
    mode when forced on."""
    from repro.core.wire import resolve_wire_dtype
    wire = resolve_wire_dtype(wire_dtype)
    wire_bwd = resolve_wire_dtype(wire_dtype_bwd)
    mixed = compute_dtype is not None and compute_dtype != "float32"
    if not mixed and wire is None and wire_bwd is None:
        return None
    from repro.models.common import ComputePolicy
    return ComputePolicy(compute_dtype=compute_dtype or "float32",
                         use_fused_kernels=use_kernel,
                         wire_dtype=wire, wire_dtype_bwd=wire_bwd)


class PaperRun(NamedTuple):
    """A placed paper-mode run: ``epoch(key, st[, participation]) ->
    (st, losses)`` is the jitted round (``engine.jit_epoch``: state
    donated, ``data`` bound, ``epoch.jitted`` the ``(key, st, data[,
    participation])`` function), ``st``/``data`` its placed arguments,
    ``key`` the epoch-key stream, ``start_epoch`` the epoch to resume
    from, and ``split``/``test_x``/``test_y`` what the final evaluation
    needs."""
    epoch: Callable
    st: dict
    data: dict
    key: jax.Array
    start_epoch: int
    split: object
    test_x: jax.Array
    test_y: jax.Array


def paper_model(model, num_clients):
    """(ResNetConfig, input side) for paper mode: ``model`` names one of
    the paper's ResNets (``configs.resnet_cifar.MODELS``, width 16 on
    32x32x3 inputs); ``None`` keeps the small CPU-sized default."""
    from repro.models import resnet as R
    if model is None:
        return R.ResNetConfig(depth=8, num_classes=num_clients, width=8), 8
    from repro.configs.resnet_cifar import MODELS
    return MODELS[model](num_classes=num_clients), 32


def build_paper(*, num_clients=8, batch_size=8, model=None, sharded=False,
                use_kernel=None, lr=0.05, scheme="sfpl", alpha=1.0,
                collector="balanced", pipeline="sync", submesh=None,
                pods=None, compute_dtype="float32", wire_dtype=None,
                wire_dtype_bwd=None, resume=None):
    """Data, split model, state and the jitted epoch of one paper-mode run
    (see ``train_paper`` for the knobs), placed on one device or on the
    collector mesh. Returns a :class:`PaperRun`."""
    from repro.core import engine as E
    from repro.data import make_synthetic_cifar, partition_positive_labels
    from repro.models import resnet as R
    from repro.optim import sgd_momentum
    from repro import checkpoint as CK

    cfg, hw = paper_model(model, num_clients)
    key = jax.random.PRNGKey(0)
    tx, ty, ex, ey = make_synthetic_cifar(
        key, num_classes=num_clients, train_per_class=4 * batch_size,
        test_per_class=2 * batch_size, hw=hw)
    data = partition_positive_labels(tx, ty, num_clients)
    split = E.make_resnet_split(cfg, policy=make_compute_policy(
        compute_dtype, use_kernel, wire_dtype, wire_dtype_bwd))
    opt = sgd_momentum(lr, momentum=0.9, weight_decay=5e-4)
    st = E.init_dcml_state(key, lambda k: R.init(k, cfg), num_clients,
                           opt, opt)

    start_ep = 0
    key = jax.random.PRNGKey(1)
    if resume:
        st, key, start_ep = CK.restore_train_state(resume, st, key_ref=key)
        print(f"resumed from {resume} at epoch {start_ep}")

    common = dict(num_clients=num_clients, batch_size=batch_size)
    if sharded:
        from repro.core import engine_dist as ED
        n_dev = len(jax.devices())
        if scheme == "sflv2":
            shards = ED.fit_shards(num_clients, batch_size, scheme="sflv2")
            mesh = ED.make_data_mesh(shards)
            print(f"sharded SFLv2: server stream over a {shards}-way mesh "
                  f"({n_dev} device(s)), sequential visitation preserved")
            epoch = ED.make_sflv2_epoch_sharded(split, opt, opt, data,
                                                mesh=mesh, **common)
        else:
            shards = ED.fit_shards(num_clients, batch_size, alpha=alpha,
                                   collector_mode=collector,
                                   collector_pipeline=pipeline,
                                   collector_submesh=submesh, pods=pods,
                                   wire_dtype=wire_dtype,
                                   wire_dtype_bwd=wire_dtype_bwd)
            mesh = ED.make_data_mesh(shards, pods=pods)
            print(f"sharded SFPL: {shards}-way data mesh over {n_dev} "
                  f"device(s), collector={collector}, alpha={alpha}, "
                  f"pipeline={pipeline}, submesh={submesh}, pods={pods}, "
                  f"use_kernel={use_kernel}, compute_dtype={compute_dtype}, "
                  f"wire_dtype={wire_dtype}, wire_dtype_bwd={wire_dtype_bwd}")
            data = ED.shard_client_data(data, mesh)
            st = ED.shard_dcml_state(st, mesh)
            epoch = ED.make_sfpl_epoch_sharded(
                split, opt, opt, data, mesh=mesh, use_kernel=use_kernel,
                alpha=alpha, collector_mode=collector,
                collector_pipeline=pipeline, collector_submesh=submesh,
                wire_dtype=wire_dtype, wire_dtype_bwd=wire_dtype_bwd,
                **common)
    elif scheme == "sflv2":
        epoch = E.jit_epoch(lambda k, s, d: E.sflv2_epoch(
            k, s, d, split, opt, opt, **common), data,
            num_clients=num_clients)
    else:
        epoch = E.jit_epoch(lambda k, s, d, m=None: E.sfpl_epoch(
            k, s, d, split, opt, opt, alpha=alpha, participation=m,
            **common), data, num_clients=num_clients, alpha=alpha)
    return PaperRun(epoch, st, data, key, start_ep, split, ex, ey)


def train_paper(*, num_clients=8, epochs=4, batch_size=8, sharded=False,
                use_kernel=None, model=None, lr=0.05,
                scheme="sfpl", alpha=1.0, collector="balanced",
                pipeline="sync", submesh=None, pods=None,
                compute_dtype="float32", wire_dtype=None,
                wire_dtype_bwd=None, log_every=1,
                ckpt=None, ckpt_every=0, resume=None,
                straggler_timeout=None, drop_rate=0.0, straggler_rate=0.0,
                straggler_delay=1.0, fault_seed=0):
    """DCML rounds on synthetic CIFAR, one client per class (only positive
    labels). ``model`` picks one of the paper's ResNets (``"resnet8"``,
    ``"resnet32"``, ``"resnet56"``: width 16, 32x32x3 inputs); ``None``
    runs the small CPU-sized default (depth 8, width 8, 8x8 inputs).
    ``scheme`` picks SFPL (Algorithm 1 + 2) or the SFLv2 baseline;
    ``sharded`` runs the same round body on a mesh over all visible devices
    (SFPL: clients + pooled batch sharded, collector as all_to_all in
    ``collector`` mode with flush threshold ``alpha``; SFLv2: the server
    stream sharded over the batch axis, visitation order preserved).
    ``compute_dtype="bfloat16"`` switches the split model onto the
    mixed-precision ``ComputePolicy`` path: f32 master params and BN
    stats, bf16 compute and smashed-data exchange, fused Pallas epilogues
    on TPU. ``wire_dtype`` (sharded SFPL) narrows the exchange payload
    independently of the compute dtype — int8/fp8 wires quantize per row
    right before each collective (``core.wire``); ``wire_dtype_bwd``
    does the same for the routed-back gradient rows. ``pods`` splits the
    sharded SFPL mesh into the 2-D ``("pod", "data")`` multi-host
    topology (one pod per host process under
    ``launch.multihost.initialize``; also works single-process for
    schedule parity testing).

    Fault tolerance (SFPL only): ``drop_rate`` / ``straggler_rate`` drive
    a deterministic :class:`~repro.core.faults.FaultPlan` whose per-epoch
    participation mask is threaded into the round — absent clients'
    activations are masked out of pooling/BN/loss and their local state is
    frozen for the epoch. ``straggler_timeout=None`` WAITS for stragglers
    (the host stalls); a finite timeout DROPS-AND-MASKS them. A draw that
    would empty a flush group has its lowest-index client revived (logged).
    ``ckpt`` + ``ckpt_every`` snapshot the full training state (params,
    optimizer, BN stats, PRNG key, epoch) every N epochs; ``resume``
    restores such a snapshot and continues bit-compatibly — on a sharded
    mesh only process 0 writes, but every process calls the (collective)
    save."""
    from repro.core.evaluate import evaluate_split_noniid
    from repro.core.faults import FaultPlan, ensure_group_survivor
    from repro import checkpoint as CK

    plan = None
    if drop_rate or straggler_rate:
        if scheme != "sfpl":
            raise ValueError("elastic participation (drop/straggler rates) "
                             "requires --scheme sfpl")
        plan = FaultPlan(num_clients, seed=fault_seed, drop_rate=drop_rate,
                         straggler_rate=straggler_rate,
                         straggler_delay=straggler_delay)

    run = build_paper(num_clients=num_clients, batch_size=batch_size,
                      model=model, sharded=sharded, use_kernel=use_kernel,
                      lr=lr, scheme=scheme, alpha=alpha,
                      collector=collector, pipeline=pipeline,
                      submesh=submesh, pods=pods,
                      compute_dtype=compute_dtype, wire_dtype=wire_dtype,
                      wire_dtype_bwd=wire_dtype_bwd, resume=resume)
    st, key = run.st, run.key

    t0 = time.time()
    mean_losses = []
    for ep in range(run.start_epoch, epochs):
        mask = None
        if plan is not None:
            mask, wait = plan.participation(
                ep, straggler_timeout=straggler_timeout)
            mask, revived = ensure_group_survivor(mask, num_clients,
                                                  alpha=alpha)
            if revived:
                print(f"epoch {ep:3d} revived clients {revived} (their "
                      f"flush group would have no survivor)", flush=True)
            print(f"epoch {ep:3d} participation {int(mask.sum())}/"
                  f"{num_clients} (straggler wait {wait:.2f}s)", flush=True)
            if wait:
                time.sleep(wait)
        key, ke = jax.random.split(key)
        st, losses = run.epoch(ke, st, mask)
        mean_losses.append(float(losses.mean()))
        if ep % log_every == 0 or ep == epochs - 1:
            print(f"epoch {ep:3d} loss {mean_losses[-1]:.4f} "
                  f"({time.time()-t0:.1f}s)", flush=True)
        if ckpt and ckpt_every and (ep + 1) % ckpt_every == 0:
            CK.save_train_state(ckpt, st, key=key, epoch=ep + 1)
            print(f"epoch {ep:3d} checkpoint -> {ckpt}", flush=True)
    if ckpt:
        CK.save_train_state(ckpt, st, key=key, epoch=epochs)
        print(f"saved final training state to {ckpt}")
    rep = evaluate_split_noniid(st, run.split, run.test_x, run.test_y,
                                num_clients, rmsd=False,
                                batch=2 * batch_size)
    print(f"non-IID accuracy {rep['accuracy']:.1f}% "
          f"(chance {100.0 / num_clients:.1f}%)")
    return mean_losses


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default="qwen3-8b")
    ap.add_argument("--steps", type=int, default=50)
    ap.add_argument("--batch", type=int, default=8)
    ap.add_argument("--seq", type=int, default=64)
    ap.add_argument("--smoke", action="store_true", default=True)
    ap.add_argument("--full", dest="smoke", action="store_false")
    ap.add_argument("--sfpl", action="store_true")
    ap.add_argument("--lr", type=float, default=None,
                    help="default 3e-3 (LM mode) / 0.05 (--paper)")
    ap.add_argument("--optimizer", default="adamw",
                    help="LM mode only; --paper is SGD-momentum (paper)")
    ap.add_argument("--ckpt")
    ap.add_argument("--paper", action="store_true",
                    help="SFPL round engine on synthetic CIFAR")
    ap.add_argument("--sharded", action="store_true",
                    help="mesh-sharded engine (with --paper)")
    ap.add_argument("--use-kernel", dest="use_kernel", action="store_true",
                    default=None,
                    help="force the Pallas collector bucket kernels on "
                         "(default: auto — on when the backend is TPU)")
    ap.add_argument("--no-kernel", dest="use_kernel", action="store_false",
                    help="force the Pallas collector bucket kernels off")
    ap.add_argument("--scheme", default="sfpl", choices=("sfpl", "sflv2"),
                    help="paper mode: DCML scheme to run")
    ap.add_argument("--alpha", type=float, default=1.0,
                    help="SFPL collector accumulation threshold")
    ap.add_argument("--collector", default="balanced",
                    choices=("balanced", "uniform"),
                    help="sharded SFPL collector permutation mode")
    ap.add_argument("--pipeline", default="sync",
                    choices=("sync", "double_buffered"),
                    help="sharded SFPL collector pipeline: sync (one "
                         "blocking exchange) or double_buffered (per-"
                         "flush-group exchange overlapping the next "
                         "group's client forward)")
    ap.add_argument("--submesh", dest="submesh", action="store_true",
                    default=None,
                    help="force sub-mesh streaming on: each flush group's "
                         "exchange is a dense zero-slack collective over "
                         "its owning shard slice (default: auto — on when "
                         "the balanced grouped layout qualifies)")
    ap.add_argument("--pods", type=int, default=None,
                    help="split the sharded SFPL mesh into this many pods "
                         "(the 2-D ('pod', 'data') multi-host topology; "
                         "default: single-pod 1-D mesh)")
    ap.add_argument("--no-submesh", dest="submesh", action="store_false",
                    help="force the whole-mesh streaming fallback")
    ap.add_argument("--compute-dtype", dest="compute_dtype",
                    default="float32", choices=("float32", "bfloat16"),
                    help="paper mode: split-model compute dtype — bfloat16 "
                         "keeps f32 master params/BN stats/loss but runs "
                         "convs, BN+ReLU epilogues, and the smashed-data "
                         "exchange in bf16 (half the collector payload)")
    from repro.core.wire import WIRE_DTYPE_NAMES
    ap.add_argument("--wire-dtype", dest="wire_dtype", default=None,
                    choices=WIRE_DTYPE_NAMES,
                    help="sharded SFPL: on-wire dtype of the smashed-data "
                         "exchange, independent of --compute-dtype — "
                         "int8/float8_e4m3 quantize per row (f32 scales "
                         "ride the same collective); default: ship rows "
                         "as computed")
    ap.add_argument("--wire-dtype-bwd", dest="wire_dtype_bwd", default=None,
                    choices=WIRE_DTYPE_NAMES,
                    help="sharded SFPL: wire dtype of the routed-back "
                         "gradient rows (default: exact — the backward "
                         "leg is the more quantization-sensitive one)")
    ap.add_argument("--model", default=None,
                    choices=("resnet8", "resnet32", "resnet56"),
                    help="paper mode: one of the paper's ResNets at "
                         "published width (16, 32x32x3 inputs); default: "
                         "a small CPU-sized ResNet-8 (width 8, 8x8 inputs)")
    ap.add_argument("--clients", type=int, default=8)
    ap.add_argument("--epochs", type=int, default=4)
    ap.add_argument("--ckpt-every", dest="ckpt_every", type=int, default=0,
                    help="paper mode: save the full training state "
                         "(params, optimizer, BN stats, PRNG key, epoch) "
                         "to --ckpt every N epochs (0: final only)")
    ap.add_argument("--resume",
                    help="paper mode: restore a --ckpt training-state "
                         "snapshot and continue from its epoch")
    ap.add_argument("--straggler-timeout", dest="straggler_timeout",
                    type=float, default=None,
                    help="straggler policy: None waits for stragglers, a "
                         "finite timeout drops-and-masks clients slower "
                         "than it")
    ap.add_argument("--drop-rate", dest="drop_rate", type=float,
                    default=0.0,
                    help="per-(epoch, client) dropout probability "
                         "(deterministic FaultPlan; absent clients are "
                         "masked out of the round)")
    ap.add_argument("--straggler-rate", dest="straggler_rate", type=float,
                    default=0.0,
                    help="per-(epoch, client) straggler probability")
    ap.add_argument("--straggler-delay", dest="straggler_delay", type=float,
                    default=1.0,
                    help="seconds a straggler lags (see "
                         "--straggler-timeout)")
    ap.add_argument("--fault-seed", dest="fault_seed", type=int, default=0,
                    help="FaultPlan seed — the whole fault schedule is a "
                         "pure function of (seed, epoch)")
    args = ap.parse_args()
    enable_compile_cache()
    if args.paper:
        losses = train_paper(num_clients=args.clients, epochs=args.epochs,
                             batch_size=args.batch, sharded=args.sharded,
                             use_kernel=args.use_kernel, model=args.model,
                             scheme=args.scheme, alpha=args.alpha,
                             collector=args.collector,
                             pipeline=args.pipeline, submesh=args.submesh,
                             pods=args.pods,
                             compute_dtype=args.compute_dtype,
                             wire_dtype=args.wire_dtype,
                             wire_dtype_bwd=args.wire_dtype_bwd,
                             lr=args.lr if args.lr is not None else 0.05,
                             ckpt=args.ckpt, ckpt_every=args.ckpt_every,
                             resume=args.resume,
                             straggler_timeout=args.straggler_timeout,
                             drop_rate=args.drop_rate,
                             straggler_rate=args.straggler_rate,
                             straggler_delay=args.straggler_delay,
                             fault_seed=args.fault_seed)
    else:
        losses = train_lm(args.arch, steps=args.steps, batch=args.batch,
                          seq=args.seq, smoke=args.smoke, sfpl=args.sfpl,
                          lr=args.lr if args.lr is not None else 3e-3,
                          optimizer=args.optimizer, ckpt=args.ckpt)
    if losses:
        print(f"first loss {losses[0]:.4f} -> last loss {losses[-1]:.4f}")


if __name__ == "__main__":
    main()
