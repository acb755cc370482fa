"""Smoke test of the SFPL round on a TPU, through ``repro.launch.train``.

    python chip_smoke.py             # one chip: single, control, mesh1, bf16_int8
    python chip_smoke.py --chips 4   # four chips: the mesh phase only

One chip runs the paper's CIFAR-10 setup at published width: ResNet-32
(width 16, 32x32x3 inputs), 10 single-class clients, per-client batch 64,
2 epochs of 4 steps, built by ``train.build_paper`` exactly as ``python -m
repro.launch.train --paper --model resnet32 --clients 10 --batch 64``
builds it. Phases:

  single     the default single-device engine (f32, no kernels): the
             reference;
  control    the same engine with another pool permutation at every step;
  mesh1      ``--sharded`` on a 1-way mesh, collector kernels on;
  bf16_int8  ``--sharded --compute-dtype bfloat16 --wire-dtype int8``.

``--chips 4`` runs the paper's CIFAR-100 setup instead: ResNet-56, 100
single-class clients (25 per chip), 2 epochs with ``pipeline=sync,
alpha=1.0`` and with ``pipeline=double_buffered, alpha=0.5`` (sub-mesh
routing chosen by the layout), each against the single-device engine (the
oracle, and its control) on the same data.

Every phase compiles its epoch ahead of time, counts the Pallas kernels in
the compiled module (a kernel that is missing fell back to its reference
path, and fails the phase), runs its epochs, checks that the losses are
finite and that the epoch-end ClientFedServer left every client the same
non-BN params. Every phase but the reference starts each epoch from the
reference's state at that epoch's start, so each epoch — the first, and
the one after the reference's FedAvg — is checked from identical state:
its first ``CHECKED_STEPS`` losses must stay within ``TOL`` of the
reference's. Compile and epoch seconds are printed, never checked. The
last line of stdout is one JSON object naming the device; without a TPU
the script exits non-zero before any phase.
"""
from __future__ import annotations

import argparse
import json
import math
import sys
import time
from pathlib import Path

sys.path.insert(0, str(Path(__file__).resolve().parent / "src"))

import jax  # noqa: E402
import numpy as np  # noqa: E402

from repro.core.bn_policy import is_bn_path  # noqa: E402
from repro.launch import train as T  # noqa: E402
from repro.launch.compile_cache import enable_compile_cache  # noqa: E402
from repro.roofline.hlo import pallas_kernel_counts  # noqa: E402

# Parity with the reference from identical state. A sharded round shuffles
# the pool with another permutation, and the server update is
# permutation-invariant, so step 0 differs only in float reduction order
# and step 1 adds one update whose client gradients were routed back
# through the exchange. Those two steps of every epoch are checked, as
# absolute loss gaps. Later steps are printed: the early high-loss steps
# of ResNet-32/56 at lr 0.05 amplify reduction-order noise, which the
# control phase (the reference engine itself under another permutation)
# measures on the same chip. f32 matmuls run at PRECISION (full f32): at
# the TPU's default (one bf16 pass) step 1 moves 1.7e-4 on ResNet-32.
# Each bound sits between the largest sound gap and the largest gap with
# a fault (each client's activation gradients rolled to its neighbour),
# both read on a v5e over steps 0-1 of both epochs: f32 1.74e-5 (the
# control) against 5.85e-3; bf16 + int8 3.60e-3 against 8.77e-3, whose
# geometric mean is the bound. PERF.md has the readings.
PRECISION = "highest"
CHECKED_STEPS = 2
TOL = {"f32": 5e-4, "bf16_int8": 5.6e-3}
# relative spread of the FedAvg'd params across clients (exact: 0)
FEDAVG_TOL = 1e-6
CONTROL_SEED = 2

COLLECT = ("sfpl_bucket_permute", "sfpl_unbucket_permute")
MIXED = ("sfpl_bn_act", "sfpl_xent_fwd", "sfpl_xent_bwd",
         "sfpl_quant_bucket_permute", "sfpl_dequant_unbucket_permute")


def run_phase(name, *, epochs, expect, starts=None, key=None, **build):
    """Build one paper-mode run (``train.build_paper``), compile its epoch
    and run ``epochs`` epochs with ``train_paper``'s epoch keys, or with
    those drawn from ``key``. Epoch ``e`` starts from the host state tree
    ``starts[e]`` placed in the run's layout when ``starts`` is given, and
    from the previous epoch's output otherwise. Returns ``(losses, seen,
    st)``: the ``(epochs, steps)`` losses, the host state each epoch
    started from, and the final state."""
    run = T.build_paper(**build)
    layout = jax.tree_util.tree_map(lambda a: a.sharding, run.st)
    key, ke = jax.random.split(run.key if key is None else key)
    t0 = time.perf_counter()
    compiled = run.epoch.jitted.lower(ke, run.st, run.data).compile()
    compile_s = time.perf_counter() - t0
    counts = pallas_kernel_counts(compiled.as_text())
    mem = compiled.memory_analysis()
    print(f"[{name}] tpu_custom_call per kernel: {counts}", flush=True)
    if mem is not None:
        print(f"[{name}] device bytes: arguments "
              f"{mem.argument_size_in_bytes} temp {mem.temp_size_in_bytes} "
              f"output {mem.output_size_in_bytes}", flush=True)
    missing = [k for k in expect if not counts.get(k)]
    if missing:
        raise SystemExit(f"[{name}] kernels missing from the compiled "
                         f"epoch (reference fallback): {missing}")
    steps = run.data["y"].shape[1] // build["batch_size"]
    st, losses, seen, secs = run.st, [], [], []
    for ep in range(epochs):
        if ep:
            key, ke = jax.random.split(key)
        if starts is not None:
            st = jax.device_put(starts[ep], layout)
        seen.append(jax.device_get(st))
        t = time.perf_counter()
        st, l = jax.block_until_ready(compiled(ke, st, run.data))
        secs.append(time.perf_counter() - t)
        losses.append(np.asarray(l, dtype=np.float64))
        check_fedavg(f"{name} epoch {ep}", st)
    losses = np.stack(losses)
    if losses.shape != (epochs, steps) or not np.isfinite(losses).all():
        raise SystemExit(f"[{name}] expected {epochs}x{steps} finite "
                         f"losses, got {losses}")
    print(f"[{name}] first-step loss {float(losses[0, 0])!r} (ln "
          f"{build['num_clients']} = {math.log(build['num_clients'])!r})",
          flush=True)
    print(f"[{name}] losses per epoch {losses.tolist()}", flush=True)
    print(f"[{name}] compile s {compile_s!r}; epoch s {secs}", flush=True)
    return losses, seen, st


def check_fedavg(name, st):
    """Algorithm 2's ClientFedServer: after the epoch every client holds
    the average of the non-BN client params (on a mesh, an all-reduce
    over the client shards)."""
    spread = 0.0
    for path, leaf in jax.tree_util.tree_leaves_with_path(st["cp"]):
        if is_bn_path(path):
            continue
        a = np.asarray(leaf, dtype=np.float64)
        scale = max(float(np.abs(a).max()), np.finfo(np.float32).tiny)
        spread = max(spread, float(np.abs(a - a[:1]).max()) / scale)
    if not spread <= FEDAVG_TOL:
        raise SystemExit(f"[{name}] FedAvg'd client params differ across "
                         f"clients by {spread} (relative; tolerance "
                         f"{FEDAVG_TOL})")
    print(f"[{name}] FedAvg spread across clients {spread!r}", flush=True)


def check_parity(name, losses, ref, tol):
    """Steps 0..CHECKED_STEPS-1 of every epoch within ``tol`` of ``ref``;
    every step's gap is printed."""
    gaps = np.abs(losses - ref)
    checked = float(gaps[:, :CHECKED_STEPS].max())
    print(f"[{name}] per-step loss gap to the reference, per epoch: "
          f"{gaps.tolist()}; steps 0-{CHECKED_STEPS - 1} of every epoch: "
          f"{checked!r} (tolerance {tol})", flush=True)
    if not checked <= tol:
        raise SystemExit(f"[{name}] loss gap {checked} exceeds {tol}")


def check_spread(name, st, n_dev):
    """Client-stacked state must be sharded over all ``n_dev`` devices,
    one equal slice each — not gathered on device 0."""
    for leaf in jax.tree_util.tree_leaves(st["cp"]):
        devs = {s.device for s in leaf.addressable_shards}
        rows = {s.data.shape[0] for s in leaf.addressable_shards}
        if len(devs) != n_dev or rows != {leaf.shape[0] // n_dev}:
            raise SystemExit(f"[{name}] client state not spread over "
                             f"{n_dev} devices: {leaf.sharding}")
    print(f"[{name}] client state spread over {n_dev} devices", flush=True)


def reference(name, paper):
    """The single-device engine, and its control under other
    permutations from the same epoch-start states."""
    ref, starts, _ = run_phase(name, expect=(), **paper)
    losses, _, _ = run_phase(f"{name}_control", expect=(), starts=starts,
                             key=jax.random.PRNGKey(CONTROL_SEED), **paper)
    check_parity(f"{name}_control", losses, ref, TOL["f32"])
    return ref, starts


ONE_CHIP = dict(model="resnet32", num_clients=10, batch_size=64, epochs=2)
FOUR_CHIPS = dict(model="resnet56", num_clients=100, batch_size=16,
                  epochs=2)


def one_chip():
    ref, starts = reference("single", ONE_CHIP)
    losses, _, _ = run_phase("mesh1", sharded=True, expect=COLLECT,
                             starts=starts, **ONE_CHIP)
    check_parity("mesh1", losses, ref, TOL["f32"])
    losses, _, _ = run_phase("bf16_int8", sharded=True,
                             compute_dtype="bfloat16", wire_dtype="int8",
                             expect=COLLECT + MIXED, starts=starts,
                             **ONE_CHIP)
    check_parity("bf16_int8", losses, ref, TOL["bf16_int8"])


def four_chips():
    # the server update is permutation-invariant, so one single-device
    # run is the oracle for both flush thresholds
    ref, starts = reference("oracle", FOUR_CHIPS)
    for name, pipeline, alpha in (("mesh4_sync", "sync", 1.0),
                                  ("mesh4_double_buffered",
                                   "double_buffered", 0.5)):
        losses, _, st = run_phase(name, sharded=True, pipeline=pipeline,
                                  alpha=alpha, expect=COLLECT,
                                  starts=starts, **FOUR_CHIPS)
        check_spread(name, st, 4)
        check_parity(name, losses, ref, TOL["f32"])


def main():
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--chips", type=int, choices=(1, 4), default=1,
                    help="1: the one-chip phases; 4: only the four-chip "
                         "mesh collector phase")
    args = ap.parse_args()
    dev = jax.devices()[0]
    print(f"device: platform={dev.platform} kind={dev.device_kind} "
          f"count={len(jax.devices())}", flush=True)
    if dev.platform != "tpu":
        raise SystemExit("no TPU found: this smoke test runs only on a chip")
    if len(jax.devices()) < args.chips:
        raise SystemExit(f"--chips {args.chips} needs {args.chips} devices")
    enable_compile_cache()
    jax.config.update("jax_default_matmul_precision", PRECISION)
    print(f"matmul precision: {PRECISION} (forced for the parity checks)",
          flush=True)
    if args.chips == 1:
        one_chip()
    else:
        four_chips()
    print(json.dumps({"ok": True, "device": {
        "platform": dev.platform, "kind": dev.device_kind,
        "count": len(jax.devices())}}))


if __name__ == "__main__":
    main()
