"""Inputs of one run, made on the device from ``--seed`` in one jitted call.

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

A seed is any whole number that fits in 64 bits; its two 32-bit halves
both enter the key.
"""
from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp


def seed_key(seed):
    seed = int(seed)
    if not 0 <= seed < 2 ** 64:
        raise ValueError(f"--seed must lie in [0, 2**64), not {seed}")
    return jax.random.fold_in(jax.random.PRNGKey(seed & 0xFFFFFFFF),
                              seed >> 32)


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


@functools.partial(jax.jit, static_argnames=("model", "fleet", "data"))
def make_inputs(key, *, model, fleet, data):
    """(state in the program's layout, client data). ``model``, ``fleet``
    and ``data`` are the configuration's groups as item tuples."""
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


def items(d):
    """A dict as a hashable, sorted item tuple (nested dicts too)."""
    return tuple(sorted((k, items(v) if isinstance(v, dict) else v)
                        for k, v in d.items()))
