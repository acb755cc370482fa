"""Evaluation drivers.

LM mode (default): perplexity / token accuracy over a token stream.
Paper mode (``--paper``): the SFPL-vs-SFLv2 comparison AT MATCHED FLEET
SIZE — both schemes trained through the same placement-agnostic round
engine (optionally ``--sharded`` on a mesh over all visible devices) and
evaluated on the same held-out set, the comparison the IoT end-to-end
evaluation (arXiv:2003.13376) argues is the only meaningful one.

Usage:
  PYTHONPATH=src python -m repro.launch.eval --arch qwen3-8b --batches 8
  PYTHONPATH=src python -m repro.launch.eval --paper [--sharded] \
      [--clients 8] [--epochs 4] [--alpha 1.0] \
      [--pipeline double_buffered] [--submesh]
"""
from __future__ import annotations

import argparse
import math

import jax
import jax.numpy as jnp

from repro.configs import get_arch
from repro.data.tokens import synthetic_token_stream
from repro.launch.compile_cache import enable_compile_cache
from repro.models.common import softmax_cross_entropy


def evaluate_lm(spec, cfg, params, *, batches=8, batch=8, seq=64, seed=0):
    """Returns {loss, ppl, token_accuracy} over the synthetic stream."""
    model = spec.model
    key = jax.random.PRNGKey(seed)

    @jax.jit
    def eval_batch(params, batch_in):
        logits, _ = model.forward(params, batch_in, cfg, training=False)
        loss = softmax_cross_entropy(logits, batch_in["labels"])
        acc = jnp.mean(
            (jnp.argmax(logits, -1) == batch_in["labels"]).astype(
                jnp.float32))
        return loss, acc

    tot_loss, tot_acc = 0.0, 0.0
    for i in range(batches):
        key, kd = jax.random.split(key)
        toks, labels = synthetic_token_stream(kd, batch=batch, seq_len=seq,
                                              vocab=cfg.vocab_size)
        b = {"tokens": toks, "labels": labels}
        if spec.family == "whisper":
            b["frame_embeds"] = jax.random.normal(
                kd, (batch, 16, cfg.d_model), jnp.float32)
        if getattr(cfg, "vision_tokens", 0):
            b["vision_embeds"] = jax.random.normal(
                kd, (batch, cfg.vision_tokens, cfg.d_model))
        loss, acc = eval_batch(params, b)
        tot_loss += float(loss)
        tot_acc += float(acc)
    loss = tot_loss / batches
    return {"loss": loss, "ppl": math.exp(min(loss, 30.0)),
            "token_accuracy": tot_acc / batches}


def evaluate_paper(*, num_clients=8, epochs=4, batch_size=8, sharded=False,
                   alpha=1.0, pipeline="sync", submesh=None, pods=None,
                   use_kernel=None, lr=0.05,
                   compute_dtype="float32", wire_dtype=None,
                   wire_dtype_bwd=None):
    """Train SFPL and SFLv2 through the unified round engine on the same
    data, fleet size, and placement (``train.build_paper``); return
    accuracy under BOTH test protocols (IID and non-IID batches) per
    scheme, so the head-to-head comparison is not confounded by the
    evaluation protocol. Each scheme is evaluated with the BN treatment it
    trained with (SFPL: CMSD, batch statistics; SFLv2: RMSD, aggregated
    running statistics). ``compute_dtype="bfloat16"`` runs both schemes on
    the mixed-precision ``ComputePolicy`` path (f32 master params and BN
    statistics); ``wire_dtype`` / ``wire_dtype_bwd`` narrow the sharded
    SFPL exchange's on-wire dtype (``core.wire`` — SFLv2 has no collector
    exchange, so the knob only affects the SFPL side of the
    comparison)."""
    from repro.core.evaluate import evaluate_split_iid, evaluate_split_noniid
    from repro.launch.train import build_paper

    def run(scheme):
        r = build_paper(num_clients=num_clients, batch_size=batch_size,
                        sharded=sharded, use_kernel=use_kernel,
                        lr=lr, scheme=scheme, alpha=alpha,
                        pipeline=pipeline, submesh=submesh, pods=pods,
                        compute_dtype=compute_dtype, wire_dtype=wire_dtype,
                        wire_dtype_bwd=wire_dtype_bwd)
        st, k = r.st, r.key
        for _ in range(epochs):
            k, ke = jax.random.split(k)
            st, _ = r.epoch(ke, st)
        rmsd = scheme == "sflv2"
        args = (st, r.split, r.test_x, r.test_y, num_clients)
        return {
            "iid": evaluate_split_iid(*args, rmsd=rmsd,
                                      batch=2 * batch_size),
            "noniid": evaluate_split_noniid(*args, rmsd=rmsd,
                                            batch=2 * batch_size),
        }

    return {"sfpl": run("sfpl"), "sflv2": run("sflv2"),
            "num_clients": num_clients, "sharded": sharded}


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default="qwen3-8b")
    ap.add_argument("--batches", type=int, default=8)
    ap.add_argument("--ckpt")
    ap.add_argument("--paper", action="store_true",
                    help="SFPL vs SFLv2 at matched fleet size")
    ap.add_argument("--sharded", action="store_true",
                    help="run both schemes on a mesh (with --paper)")
    ap.add_argument("--clients", type=int, default=8)
    ap.add_argument("--epochs", type=int, default=4)
    ap.add_argument("--alpha", type=float, default=1.0)
    ap.add_argument("--pipeline", default="sync",
                    choices=("sync", "double_buffered"),
                    help="sharded SFPL collector pipeline (with --paper "
                         "--sharded)")
    ap.add_argument("--submesh", dest="submesh", action="store_true",
                    default=None,
                    help="force sub-mesh streaming on (default: auto when "
                         "the balanced grouped layout qualifies)")
    ap.add_argument("--pods", type=int, default=None,
                    help="split the sharded mesh into this many pods (the "
                         "2-D ('pod', 'data') multi-host topology)")
    ap.add_argument("--no-submesh", dest="submesh", action="store_false",
                    help="force the whole-mesh streaming fallback")
    ap.add_argument("--use-kernel", dest="use_kernel", action="store_true",
                    default=None,
                    help="force the Pallas collector bucket kernels on "
                         "(default: auto — on when the backend is TPU)")
    ap.add_argument("--no-kernel", dest="use_kernel", action="store_false",
                    help="force the Pallas collector bucket kernels off")
    ap.add_argument("--compute-dtype", dest="compute_dtype",
                    default="float32", choices=("float32", "bfloat16"),
                    help="paper mode: split-model compute dtype (bfloat16 "
                         "= mixed precision with f32 master params)")
    from repro.core.wire import WIRE_DTYPE_NAMES
    ap.add_argument("--wire-dtype", dest="wire_dtype", default=None,
                    choices=WIRE_DTYPE_NAMES,
                    help="sharded SFPL: on-wire dtype of the smashed-data "
                         "exchange (int8/float8_e4m3 quantize per row; "
                         "default: ship rows as computed)")
    ap.add_argument("--wire-dtype-bwd", dest="wire_dtype_bwd", default=None,
                    choices=WIRE_DTYPE_NAMES,
                    help="sharded SFPL: wire dtype of the routed-back "
                         "gradient rows (default: exact)")
    args = ap.parse_args()
    enable_compile_cache()
    if args.paper:
        rep = evaluate_paper(num_clients=args.clients, epochs=args.epochs,
                             sharded=args.sharded, alpha=args.alpha,
                             pipeline=args.pipeline, submesh=args.submesh,
                             pods=args.pods, use_kernel=args.use_kernel,
                             compute_dtype=args.compute_dtype,
                             wire_dtype=args.wire_dtype,
                             wire_dtype_bwd=args.wire_dtype_bwd)
        chance = 100.0 / args.clients
        print(f"matched fleet ({args.clients} clients, "
              f"sharded={args.sharded}, chance {chance:.1f}%):")
        for scheme in ("sfpl", "sflv2"):
            r = rep[scheme]
            print(f"  {scheme:5s}  IID test {r['iid']['accuracy']:5.1f}%  "
                  f"non-IID test {r['noniid']['accuracy']:5.1f}%")
        return
    spec = get_arch(args.arch)
    cfg = spec.make_smoke_config()
    params = spec.model.init(jax.random.PRNGKey(0), cfg)
    if args.ckpt:
        from repro.checkpoint import restore_checkpoint
        params, step = restore_checkpoint(args.ckpt, params)
        print(f"restored step {step}")
    m = evaluate_lm(spec, cfg, params, batches=args.batches)
    print(f"{args.arch}: loss {m['loss']:.4f}  ppl {m['ppl']:.1f}  "
          f"token-acc {m['token_accuracy']:.3f}")


if __name__ == "__main__":
    main()
