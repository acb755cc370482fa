"""Plain reference of one SFPL round over a CIFAR ResNet (He et al. 2016,
section 4.2), split after conv1 + BN + ReLU as in SFPL (arXiv:2307.13266,
Algorithms 1 and 2).

Straight ``jax.numpy`` in float32 with every conv and matmul at
``Precision.HIGHEST``; it imports nothing of the program under test. One
round is ``steps`` local steps, then the ClientFedServer average:

1. every client runs its part on its own batch (BatchNorm over that
   client's batch);
2. the pool of all smashed rows goes through the server part in one
   batch (BatchNorm over the whole pool, mean cross-entropy over it). The
   program shuffles the pool first; a shuffle changes neither the mean
   loss nor the batch statistics, so the reference keeps the rows in
   client order;
3. the server takes one SGD-momentum step (weight decay added to the
   gradient); each client gets its rows' activation gradients back and
   takes one step of its own;
4. after the last step the clients' non-BatchNorm parameters are averaged
   (CMSD: each client keeps its own BatchNorm scale and shift).

BatchNorm running statistics do not enter the loss or any gradient during
training, so the reference does not track them.

``mode`` sets the arithmetic: ``"f32"`` for the reference itself, and
for the controls, the reference computed in the precision below the one a
traffic mix states:

* ``"bf16x3"`` (below float32 at ``highest``) — every conv and matmul as
  the three-pass bfloat16 product that ``Precision.HIGH`` computes on a
  TPU, in both passes;
* ``"fp8"`` (below bfloat16) — every conv and matmul on float8_e4m3fn
  operands, and every activation kept in float8_e4m3fn (conv and
  classifier outputs, BatchNorm outputs, residual sums, the pooled
  features), so that gradients are rounded there too; master parameters,
  BatchNorm statistics and the loss stay float32;
* ``"bf16"`` — the same with bfloat16: how far rounding alone moves the
  round when bfloat16 is the stated precision (a look, not a control).
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax import lax

HI = lax.Precision.HIGHEST
EPS = 1e-5


def _conv(x, w, stride=1):
    return lax.conv_general_dilated(
        x, w, (stride, stride), "SAME",
        dimension_numbers=("NHWC", "HWIO", "NHWC"), precision=HI)


def _dot(x, w):
    return jnp.dot(x, w, precision=HI)


def _round_to(dtype):
    return lambda x: x.astype(dtype).astype(jnp.float32)


def _split3(a):
    hi = a.astype(jnp.bfloat16).astype(jnp.float32)
    lo = (a - hi).astype(jnp.bfloat16).astype(jnp.float32)
    return hi, lo


def _three_pass(f):
    """``f`` (bilinear) with each operand split into bfloat16 high and low
    parts and the low*low product dropped, in both passes."""
    def f3(a, b):
        ah, al = _split3(a)
        bh, bl = _split3(b)
        return f(ah, bh) + f(ah, bl) + f(al, bh)

    @jax.custom_vjp
    def g(a, b):
        return f3(a, b)

    def fwd(a, b):
        return f3(a, b), (a, b)

    def bwd(res, ct):
        a, b = res
        ch, cl = _split3(ct)
        ah, al = _split3(a)
        bh, bl = _split3(b)
        da = db = 0.0
        for cc, bb in ((ch, bh), (ch, bl), (cl, bh)):
            da = da + jax.vjp(lambda a_: f(a_, bb), a)[1](cc)[0]
        for cc, aa in ((ch, ah), (ch, al), (cl, ah)):
            db = db + jax.vjp(lambda b_: f(aa, b_), b)[1](cc)[0]
        return da, db

    g.defvjp(fwd, bwd)
    return g


def ops_for(mode):
    """(conv, dot, store) of the arithmetic ``mode``: ``store`` rounds an
    activation to the dtype it is kept in."""
    if mode == "f32":
        return _conv, _dot, lambda x: x
    if mode == "bf16x3":
        return (lambda x, w, stride=1: _three_pass(
                    functools.partial(_conv, stride=stride))(x, w),
                _three_pass(_dot), lambda x: x)
    q = _round_to({"fp8": jnp.float8_e4m3fn, "bf16": jnp.bfloat16}[mode])
    return (lambda x, w, stride=1: q(_conv(q(x), q(w), stride)),
            lambda x, w: q(_dot(q(x), q(w))), q)


def batchnorm(p, x):
    axes = tuple(range(x.ndim - 1))
    mean = jnp.mean(x, axis=axes)
    var = jnp.mean(jnp.square(x - mean), axis=axes)
    return (x - mean) / jnp.sqrt(var + EPS) * p["scale"] + p["bias"]


def client_forward(cp, x, ops):
    conv, _, store = ops
    return store(jax.nn.relu(batchnorm(cp["bn1"],
                                       conv(store(x), cp["conv1"]["w"]))))


def server_logits(sp, a, model, ops):
    conv, dot, store = ops
    n = (model["depth"] - 2) // 6
    h = a
    for stage in range(3):
        for b in range(n):
            p = sp[f"s{stage}b{b}"]
            stride = 2 if stage > 0 and b == 0 else 1
            y = store(jax.nn.relu(batchnorm(
                p["bn1"], conv(h, p["conv1"]["w"], stride))))
            y = store(batchnorm(p["bn2"], conv(y, p["conv2"]["w"])))
            if "proj" in p:
                h = store(batchnorm(p["bn_proj"],
                                    conv(h, p["proj"]["w"], stride)))
            h = jax.nn.relu(store(y + h))
    h = store(jnp.mean(h, axis=(1, 2)))
    return store(dot(h, sp["fc"]["w"]) + store(sp["fc"]["b"]))


def cross_entropy(logits, y):
    lse = jax.nn.logsumexp(logits, axis=-1)
    picked = jnp.take_along_axis(logits, y[:, None], axis=-1)[:, 0]
    return jnp.mean(lse - picked)


def _is_bn(path):
    return any(str(getattr(k, "key", k)).startswith("bn") for k in path)


def sgd(p, mu, g, opt):
    def one(p_, m_, g_):
        g_ = g_ + opt["weight_decay"] * p_
        m_ = opt["momentum"] * m_ + g_
        return p_ - opt["lr"] * m_, m_
    out = jax.tree_util.tree_map(one, p, mu, g)
    pick = lambda i: jax.tree_util.tree_map(
        lambda t: t[i], out, is_leaf=lambda t: isinstance(t, tuple))
    return pick(0), pick(1)


def leaf_norms(tree):
    return [jnp.sqrt(jnp.sum(jnp.square(a)))
            for a in jax.tree_util.tree_leaves(tree)]


@functools.partial(jax.jit, static_argnames=("model", "fleet", "opt",
                                             "mode"))
def sfpl_round(params, data, *, model, fleet, opt, mode="f32"):
    """``params = {"cp": client-stacked, "sp": server}`` and momentum
    starting at zero. Returns ``(losses, params_after, first_grad_norms)``:
    the pooled loss of each local step, the parameters after the round's
    average, and the norm of each leaf's step-0 gradient (client leaves
    over the whole client stack), in ``{"cp", "sp"}`` leaf order."""
    model, fleet, opt = dict(model), dict(fleet), dict(opt)
    ops = ops_for(mode)
    B, steps = fleet["per_client_batch"], fleet["steps_per_round"]
    N = data["x"].shape[0]

    def server_loss(sp, pool, y):
        return cross_entropy(server_logits(sp, pool, model, ops), y)

    def step(carry, t):
        cp, sp, cmu, smu = carry
        xb = lax.dynamic_slice_in_dim(data["x"], t * B, B, axis=1)
        yb = lax.dynamic_slice_in_dim(data["y"], t * B, B, axis=1)
        A, back = jax.vjp(lambda c: jax.vmap(
            lambda cc, x: client_forward(cc, x, ops))(c, xb), cp)
        pool = A.reshape((N * B,) + A.shape[2:])
        loss, (g_sp, g_pool) = jax.value_and_grad(
            server_loss, argnums=(0, 1))(sp, pool, yb.reshape(N * B))
        (g_cp,) = back(g_pool.reshape(A.shape))
        sp, smu = sgd(sp, smu, g_sp, opt)
        cp, cmu = sgd(cp, cmu, g_cp, opt)
        norms = jnp.stack(leaf_norms({"cp": g_cp, "sp": g_sp}))
        return (cp, sp, cmu, smu), (loss, norms)

    zeros = lambda t: jax.tree_util.tree_map(jnp.zeros_like, t)
    carry = (params["cp"], params["sp"], zeros(params["cp"]),
             zeros(params["sp"]))
    (cp, sp, _, _), (losses, norms) = lax.scan(step, carry,
                                                jnp.arange(steps))
    cp = jax.tree_util.tree_map_with_path(
        lambda path, a: a if _is_bn(path) else jnp.broadcast_to(
            jnp.mean(a, axis=0, keepdims=True), a.shape), cp)
    return losses, {"cp": cp, "sp": sp}, norms[0]
