"""The CIFAR ResNet family: the paper's split ResNet-8/32/56 (He et al.
arXiv:1512.03385 section 4.2, split after conv1 + BN + ReLU as in SFPL,
arXiv:2307.13266), behind the interface that ``spec.py`` names.

Inputs of one run, made on the device from the seed in one jitted call:

* Data: the CIFAR-shaped class-template images of
  ``repro.data.synthetic.make_synthetic_cifar`` (a smooth random template
  per class, bilinearly upsampled from a coarse grid and scaled to unit
  deviation, plus Gaussian noise, each image rolled by a random shift),
  copied here so that the benchmark owns its traffic. Client ``k`` holds
  only class ``k`` (SFPL's positive-label partition), one round's worth of
  rows: ``steps_per_round * per_client_batch`` each, all distinct.
* Weights: the client and server parts of the CIFAR ResNet in the
  program's parameter layout (He-normal convs, LeCun-normal classifier,
  BatchNorm scale 1, bias 0, running mean 0 and variance 1), every client
  starting from the same client part, optimizer momentum at zero.

Operations and bytes from the configuration's ``model`` and ``fleet``:

* ``forward_macs`` — multiply-accumulates of one sample's forward pass
  through the client part (conv1) and the server part (three residual
  stages and the classifier). A training sample costs three forward
  passes' worth (forward, input gradient, weight gradient), so the model
  step's FLOPs per sample are ``6 * forward_macs``; the client forward
  that the client update recomputes is not counted.
* ``table_iv_client`` — the paper's Table IV count of the client part:
  conv1 MACs plus BN's scale and shift per element (475,136 at width 16).
* ``bn_layers`` / ``bn_act_cost`` — the BN+activation epilogues one
  round applies, each once per forward (the fused ``sfpl_bn_act``
  kernel's unpadded input and output bytes, and one multiply, one add and
  at most one max per element).
* ``smashed_elems`` — the elements of one smashed row, the client part's
  output that the collector moves.
"""
from __future__ import annotations

import functools
import json
import math

import jax
import jax.numpy as jnp

from chip import flops as F
from chip import gen

SPLIT = ("repro.core.engine", "make_resnet_split")


def build(cfg, traffic, **overrides):
    """The round through the program's launcher, ``build_paper``;
    ``overrides`` replace its arguments (calibration's looks only)."""
    from repro.launch import train as T
    m, fl, opt = cfg["model"], cfg["fleet"], cfg["optimizer"]
    if (m["depth"], m["width"], m["input_hw"]) == (8, 8, 8):
        model = None            # the launcher's small CPU-sized ResNet-8
    else:
        model = f"resnet{m['depth']}"
    return T.build_paper(**dict(
        num_clients=fl["num_clients"], batch_size=fl["per_client_batch"],
        model=model, lr=opt["lr"], sharded=traffic["engine"] == "sharded",
        compute_dtype=traffic["compute_dtype"],
        wire_dtype=traffic["wire_dtype"], pipeline=traffic["pipeline"],
        alpha=traffic["alpha"]) | overrides)


def tiny(cfg):
    """``cfg`` on the launcher's CPU-sized ResNet-8 (width 8, 8x8 inputs),
    4 clients x 4 rows."""
    cfg = json.loads(json.dumps(cfg))
    cfg["model"].update(depth=8, width=8, input_hw=8, num_classes=4)
    cfg["fleet"].update(num_clients=4, per_client_batch=4)
    return cfg


def _smooth(key, hw, grid, ch):
    coarse = jax.random.normal(key, (grid, grid, ch))
    img = jax.image.resize(coarse, (hw, hw, ch), method="bilinear")
    return img / (jnp.std(img) + 1e-6)


def _he(key, shape, fan_in):
    return jax.random.normal(key, shape) * math.sqrt(2.0 / fan_in)


def _bn(c):
    return ({"scale": jnp.ones((c,)), "bias": jnp.zeros((c,))},
            {"mean": jnp.zeros((c,)), "var": jnp.ones((c,)),
             "count": jnp.zeros(())})


def init_params(key, model):
    """({"client", "server"} params, {"client", "server"} BN state)."""
    w, cin0 = model["width"], model["input_channels"]
    kc, ks, kf = jax.random.split(key, 3)
    cbn_p, cbn_s = _bn(w)
    client = {"conv1": {"w": _he(kc, (3, 3, cin0, w), 9 * cin0)},
              "bn1": cbn_p}
    server, sstate = {}, {}
    n = (model["depth"] - 2) // 6
    cin = w
    for stage, cout in enumerate((w, 2 * w, 4 * w)):
        for b in range(n):
            k = jax.random.fold_in(ks, stage * 100 + b)
            k1, k2, k3 = jax.random.split(k, 3)
            p, s = {}, {}
            p["conv1"] = {"w": _he(k1, (3, 3, cin, cout), 9 * cin)}
            p["bn1"], s["bn1"] = _bn(cout)
            p["conv2"] = {"w": _he(k2, (3, 3, cout, cout), 9 * cout)}
            p["bn2"], s["bn2"] = _bn(cout)
            if stage > 0 and b == 0:
                p["proj"] = {"w": _he(k3, (1, 1, cin, cout), cin)}
                p["bn_proj"], s["bn_proj"] = _bn(cout)
            server[f"s{stage}b{b}"], sstate[f"s{stage}b{b}"] = p, s
            cin = cout
    server["fc"] = {
        "w": jax.random.normal(kf, (4 * w, model["num_classes"]))
        * math.sqrt(1.0 / (4 * w)),
        "b": jnp.zeros((model["num_classes"],))}
    return ({"client": client, "server": server},
            {"client": {"bn1": cbn_s}, "server": sstate})


def make_data(key, model, fleet, data):
    """``{"x": (N, n, hw, hw, c) f32, "y": (N, n) int32}``, client k
    holding class k."""
    N = fleet["num_clients"]
    n = fleet["steps_per_round"] * fleet["per_client_batch"]
    hw, ch = model["input_hw"], model["input_channels"]
    kt, kn, ka, kr = jax.random.split(key, 4)
    templates = jax.vmap(lambda k: _smooth(k, hw, data["template_grid"],
                                           ch))(jax.random.split(kt, N))
    x = templates[:, None] + data["noise"] * jax.random.normal(
        kn, (N, n, hw, hw, ch))
    s = data["max_shift"]
    shifts = jax.random.randint(kr, (N, n, 2), -s, s + 1)

    def roll(img, r):
        return jnp.roll(jnp.roll(img, r[0], axis=0), r[1], axis=1)
    x = jax.vmap(jax.vmap(roll))(x, shifts)
    y = jnp.broadcast_to(jnp.arange(N, dtype=jnp.int32)[:, None], (N, n))
    return {"x": x.astype(jnp.float32), "y": y}


def make_inputs(key, cfg):
    """(state in the program's layout, client data) from ``key``."""
    return _inputs(key, model=gen.items(cfg["model"]),
                   fleet=gen.items(cfg["fleet"]),
                   data=gen.items(cfg["data"]))


@functools.partial(jax.jit, static_argnames=("model", "fleet", "data"))
def _inputs(key, *, model, fleet, data):
    model, fleet, data = dict(model), dict(fleet), dict(data)
    kw, kd = jax.random.split(key)
    params, bn = init_params(kw, model)
    N = fleet["num_clients"]
    rep = lambda t: jax.tree_util.tree_map(
        lambda a: jnp.broadcast_to(a[None], (N,) + a.shape), t)
    zeros = lambda t: jax.tree_util.tree_map(jnp.zeros_like, t)
    st = {"cp": rep(params["client"]), "cbn": rep(bn["client"]),
          "sp": params["server"], "sbn": bn["server"],
          "copt": {"mu": zeros(rep(params["client"]))},
          "sopt": {"mu": zeros(params["server"])},
          "step": jnp.zeros((), jnp.int32)}
    return st, make_data(kd, model, fleet, data)


def blocks_per_stage(depth):
    if (depth - 2) % 6:
        raise ValueError(f"a CIFAR ResNet has depth 6n+2, not {depth}")
    return (depth - 2) // 6


def conv_macs(hw_out, k, cin, cout):
    return hw_out * hw_out * k * k * cin * cout


def stage_convs(model):
    """``[(stage, hw_out, k, cin, cout)]`` of every server conv."""
    w, hw = model["width"], model["input_hw"]
    out, cin = [], w
    for stage, cout in enumerate((w, 2 * w, 4 * w)):
        for b in range(blocks_per_stage(model["depth"])):
            stride = 2 if stage > 0 and b == 0 else 1
            hw_s = hw // (2 ** stage)
            out.append((stage, hw_s, 3, cin, cout))        # conv1
            out.append((stage, hw_s, 3, cout, cout))       # conv2
            if stride != 1 or cin != cout:
                out.append((stage, hw_s, 1, cin, cout))    # projection
            cin = cout
    return out


def client_macs(model):
    return conv_macs(model["input_hw"], 3, model["input_channels"],
                     model["width"])


def server_macs(model):
    convs = sum(conv_macs(hw, k, ci, co)
                for _, hw, k, ci, co in stage_convs(model))
    return convs + 4 * model["width"] * model["num_classes"]


def forward_macs(model):
    return client_macs(model) + server_macs(model)


def train_flops_per_sample(model):
    return 6 * forward_macs(model)


def table_iv_client(model):
    hw = model["input_hw"]
    return client_macs(model) + 2 * model["width"] * hw * hw


def smashed_elems(model):
    return model["input_hw"] ** 2 * model["width"]


def bn_layers(model):
    """``[(elements per sample, channels, relu)]`` of every
    BN+activation epilogue of one forward pass, client first."""
    hw, w = model["input_hw"], model["width"]
    out = [(hw * hw * w, w, True)]
    for stage, cout in enumerate((w, 2 * w, 4 * w)):
        hw_s = hw // (2 ** stage)
        for b in range(blocks_per_stage(model["depth"])):
            out.append((hw_s * hw_s * cout, cout, True))   # bn1 + relu
            out.append((hw_s * hw_s * cout, cout, False))  # bn2
            if stage > 0 and b == 0:
                out.append((hw_s * hw_s * cout, cout, False))  # bn_proj
    return out


def bn_act_cost(model, fleet, compute_dtype):
    """(ops, bytes) of one round's BN+activation epilogues, all chips:
    each reads the conv output and writes the activation once in the
    compute dtype, and reads its f32 scale and shift vectors."""
    item = F.DTYPE_BYTES[compute_dtype]
    rows = F.pool_rows(fleet)
    ops = nbytes = 0
    for elems, chans, relu in bn_layers(model):
        n = rows * elems
        ops += n * (3 if relu else 2)
        nbytes += 2 * n * item + 2 * chans * 4
    steps = fleet["steps_per_round"]
    return ops * steps, nbytes * steps
