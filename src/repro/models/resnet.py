"""CIFAR ResNet-8/32/56 (He et al. 2016), structured for splitfed learning.

Params/state are split into ``client`` and ``server`` subtrees at the paper's
cut: the client holds the initial 3x3 conv(3->16) + BN + ReLU (464 params,
475.136K flops/datapoint — Table IV), the server holds the residual stages,
the pooled head, and the classifier. BatchNorm running statistics live in a
separate ``state`` tree so the SFPL aggregation policies (RMSD / CMSD /
FedBN-exclusion) can act on them explicitly.
"""
from __future__ import annotations

import dataclasses

import jax
import jax.numpy as jnp

from repro.kernels.platform import interpret
from repro.nn.conv import conv2d_init, conv2d_apply
from repro.nn.linear import dense_init, dense_apply
from repro.nn.norm import (batchnorm_init, batchnorm_apply,
                           batchnorm_act_apply)


@dataclasses.dataclass(frozen=True)
class ResNetConfig:
    depth: int = 8                  # 8 / 32 / 56  (= 6n+2)
    num_classes: int = 10
    width: int = 16

    @property
    def blocks_per_stage(self) -> int:
        assert (self.depth - 2) % 6 == 0, self.depth
        return (self.depth - 2) // 6


# --------------------------------------------------------------------------
# init

def _block_init(key, cin, cout, stride):
    ks = jax.random.split(key, 6)
    p, s = {}, {}
    p["conv1"] = conv2d_init(ks[0], cin, cout, 3)
    p["bn1"], s["bn1"] = batchnorm_init(ks[1], cout)
    p["conv2"] = conv2d_init(ks[2], cout, cout, 3)
    p["bn2"], s["bn2"] = batchnorm_init(ks[3], cout)
    if stride != 1 or cin != cout:
        p["proj"] = conv2d_init(ks[4], cin, cout, 1)
        p["bn_proj"], s["bn_proj"] = batchnorm_init(ks[5], cout)
    return p, s


def init(key, cfg: ResNetConfig):
    kc, kb, kf = jax.random.split(key, 3)
    w = cfg.width
    client_p = {"conv1": conv2d_init(jax.random.fold_in(kc, 0), 3, w, 3)}
    bn_p, bn_s = batchnorm_init(jax.random.fold_in(kc, 1), w)
    client_p["bn1"] = bn_p
    client_s = {"bn1": bn_s}

    server_p, server_s = {}, {}
    cin = w
    for stage, cout in enumerate([w, 2 * w, 4 * w]):
        for b in range(cfg.blocks_per_stage):
            stride = 2 if (stage > 0 and b == 0) else 1
            key_b = jax.random.fold_in(kb, stage * 100 + b)
            bp, bs = _block_init(key_b, cin, cout, stride)
            server_p[f"s{stage}b{b}"] = bp
            server_s[f"s{stage}b{b}"] = bs
            cin = cout
    server_p["fc"] = dense_init(kf, 4 * w, cfg.num_classes)
    return ({"client": client_p, "server": server_p},
            {"client": client_s, "server": server_s})


# --------------------------------------------------------------------------
# apply
#
# ``policy`` (a models.common.ComputePolicy or None) selects the compute
# path.  None keeps the original unfused f32 graph bit-for-bit (the folded
# BN affine below rounds differently, so parity-pinned callers must stay
# off it).  With a policy, convs/dense run in ``policy.compute_dtype``,
# every BN (+ following ReLU, where one exists) collapses into the fused
# ``batchnorm_act_apply`` epilogue — Pallas ``bn_act`` when
# ``policy.fused()`` — while the BN statistics stay exact f32.


def _cd(policy):
    return policy.cdtype() if policy is not None and policy.mixed else None


def _bn(p, s, x, *, training, rmsd, policy=None, relu=False, valid=None):
    if policy is None:
        y, ns = batchnorm_apply(p, s, x, training=training,
                                use_running_stats=rmsd, valid=valid)
        if relu:
            y = jax.nn.relu(y)
        return y, ns
    return batchnorm_act_apply(p, s, x, training=training, relu=relu,
                               use_running_stats=rmsd,
                               use_kernel=policy.fused(),
                               interpret=interpret(),
                               valid=valid)


def client_apply(params, state, x, *, training=True, rmsd=None, policy=None):
    """x: (B, 32, 32, 3) -> smashed data (B, 32, 32, w). Returns (a, state).

    With a mixed ``policy`` the smashed data comes out in the compute
    dtype — that is the tensor the collector exchanges, at half the f32
    payload bytes for bf16."""
    if policy is not None:
        x = policy.cast(x)
    h = conv2d_apply(params["conv1"], x, compute_dtype=_cd(policy))
    h, bn1 = _bn(params["bn1"], state["bn1"], h, training=training,
                 rmsd=rmsd, policy=policy, relu=True)
    return h, {"bn1": bn1}


def _block_apply(p, s, x, stride, *, training, rmsd, policy=None, valid=None):
    ns = {}
    cd = _cd(policy)
    h = conv2d_apply(p["conv1"], x, stride=stride, compute_dtype=cd)
    h, ns["bn1"] = _bn(p["bn1"], s["bn1"], h, training=training, rmsd=rmsd,
                       policy=policy, relu=True, valid=valid)
    h = conv2d_apply(p["conv2"], h, compute_dtype=cd)
    h, ns["bn2"] = _bn(p["bn2"], s["bn2"], h, training=training, rmsd=rmsd,
                       policy=policy, valid=valid)
    if "proj" in p:
        x = conv2d_apply(p["proj"], x, stride=stride, compute_dtype=cd)
        x, ns["bn_proj"] = _bn(p["bn_proj"], s["bn_proj"], x,
                               training=training, rmsd=rmsd, policy=policy,
                               valid=valid)
    return jax.nn.relu(h + x), ns


def server_apply(params, state, a, cfg: ResNetConfig, *, training=True,
                 rmsd=None, policy=None, valid=None):
    """a: smashed data (B, 32, 32, w) -> logits. Returns (logits, state).

    ``valid`` (optional ``(B,)`` bool) marks rows that belong to absent
    clients under elastic participation: they flow through the network
    (shapes are static) but are excluded from every BN batch statistic,
    so the server's state update matches a run on the surviving rows
    alone."""
    ns = {}
    h = a if policy is None else policy.cast(a)
    for stage in range(3):
        for b in range(cfg.blocks_per_stage):
            stride = 2 if (stage > 0 and b == 0) else 1
            name = f"s{stage}b{b}"
            h, ns[name] = _block_apply(params[name], state[name], h, stride,
                                       training=training, rmsd=rmsd,
                                       policy=policy, valid=valid)
    h = jnp.mean(h, axis=(1, 2))
    return dense_apply(params["fc"], h, compute_dtype=_cd(policy)), ns


def apply(params, state, x, cfg: ResNetConfig, *, training=True, rmsd=None,
          policy=None):
    a, cs = client_apply(params["client"], state["client"], x,
                         training=training, rmsd=rmsd, policy=policy)
    logits, ss = server_apply(params["server"], state["server"], a, cfg,
                              training=training, rmsd=rmsd, policy=policy)
    return logits, {"client": cs, "server": ss}


def client_flops_per_datapoint(cfg: ResNetConfig, hw=32):
    """MAC-count of the client portion (Table IV check)."""
    conv = 3 * 3 * 3 * cfg.width * hw * hw   # 3x3 conv, stride 1, SAME
    bn = 2 * cfg.width * hw * hw             # scale + shift
    return conv + bn
