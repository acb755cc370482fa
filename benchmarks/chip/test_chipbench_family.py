"""A configuration's model is its family's business (``spec.family``).

The ResNet family makes, from a seed, the same state and data as the
harness did before families (digests of that output, at both
configurations' published sizes). A second family is added as new files
only: a toy two-layer dense split, with its config, plain reference and
limits, written to a directory that ``spec``'s lookups are pointed at,
runs through the unchanged harness to ``correct``, and with ``misroute``
planted to not ``correct``.
"""
import hashlib
import json
import textwrap

import jax
import numpy as np
import pytest

from chip import faults
from chip import gen
from chip import run as R
from chip import spec as S

SEED = 2 ** 33 + 5

# sha256 of (state, data) leaves, in tree order, that ``gen.make_inputs``
# gave before the ResNet parts moved into ``families/resnet_cifar.py``
PARENT = {
    "resnet32_cifar10": (
        "762bef926b044dfaf3920492de217ddb3773b4e587538ca1162db38dd7ba3096",
        "2173bdbb0a38cce0e1a3463b1bb8dddfe7666f8618ced545a6959c2f2f1c5176"),
    "resnet56_cifar100": (
        "b9afbcab51d6c9d71e5ef143918f00832e393cfdd5fad8160934e2b4c568aba3",
        "73108cc4d52cab97562a9948a3cd3f22684b19b78f91cee55ba94e4e6f972b48"),
}


def digest(tree):
    h = hashlib.sha256()
    for path, a in jax.tree_util.tree_leaves_with_path(jax.device_get(tree)):
        a = np.asarray(a)
        h.update(f"{jax.tree_util.keystr(path)} {a.dtype} {a.shape};"
                 .encode())
        h.update(np.ascontiguousarray(a).tobytes())
    return h.hexdigest()


@pytest.mark.parametrize("name", sorted(PARENT))
def test_resnet_inputs_are_the_parents(name):
    cfg = S.config(name)
    st, data = S.family(cfg).make_inputs(gen.seed_key(SEED), cfg)
    assert (digest(st), digest(data)) == PARENT[name]


TOY_FAMILY = '''
"""A two-layer dense split: the client part one ReLU layer, the server
part the classifier; no BatchNorm, so the client state is empty."""
import functools
import types

import jax
import jax.numpy as jnp

from chip import gen

SPLIT = (__name__, "make_split")


def make_split(cfg):
    from repro.core import engine as E
    from repro.models.common import softmax_cross_entropy

    def client_fwd(cp, cs, x, training=True, rmsd=None):
        return jax.nn.relu(x @ cp["w"] + cp["b"]), cs

    def server_loss(sp, ss, a, y, training=True, rmsd=None, valid=None):
        logits = a @ sp["w"] + sp["b"]
        return softmax_cross_entropy(logits, y), (ss, logits)

    def full_loss(p, s, x, y, training=True, rmsd=None):
        a, _ = client_fwd(p["client"], s, x)
        return server_loss(p["server"], s, a, y)
    return E.SplitModel(client_fwd, server_loss, full_loss)


def build(cfg, traffic, **overrides):
    from repro.core import engine as E
    from repro.optim import sgd_momentum
    fl, o = cfg["fleet"], cfg["optimizer"]
    split = make_split(cfg)
    sgd = sgd_momentum(o["lr"], momentum=o["momentum"],
                       weight_decay=o["weight_decay"])
    st, data = make_inputs(jax.random.PRNGKey(0), cfg)
    kw = dict(num_clients=fl["num_clients"],
              batch_size=fl["per_client_batch"])
    epoch = E.jit_epoch(lambda k, s, d: E.sfpl_epoch(
        k, s, d, split, sgd, sgd, **kw), data,
        num_clients=fl["num_clients"])
    return types.SimpleNamespace(st=st, data=data, epoch=epoch)


def make_inputs(key, cfg):
    return _inputs(key, model=gen.items(cfg["model"]),
                   fleet=gen.items(cfg["fleet"]))


@functools.partial(jax.jit, static_argnames=("model", "fleet"))
def _inputs(key, *, model, fleet):
    m, fl = dict(model), dict(fleet)
    N = fl["num_clients"]
    n = fl["steps_per_round"] * fl["per_client_batch"]
    d, h, c = m["d_in"], m["d_hidden"], m["num_classes"]
    k1, k2, k3, k4 = jax.random.split(key, 4)
    client = {"w": jax.random.normal(k1, (d, h)) / d ** 0.5,
              "b": jnp.zeros((h,))}
    server = {"w": jax.random.normal(k2, (h, c)) / h ** 0.5,
              "b": jnp.zeros((c,))}
    rep = lambda t: jax.tree_util.tree_map(
        lambda a: jnp.broadcast_to(a[None], (N,) + a.shape), t)
    zeros = lambda t: jax.tree_util.tree_map(jnp.zeros_like, t)
    st = {"cp": rep(client), "cbn": {}, "sp": server, "sbn": {},
          "copt": {"mu": zeros(rep(client))}, "sopt": {"mu": zeros(server)},
          "step": jnp.zeros((), jnp.int32)}
    means = jax.random.normal(k3, (N, 1, d))
    x = means + 0.5 * jax.random.normal(k4, (N, n, d))
    y = jnp.broadcast_to(jnp.arange(N, dtype=jnp.int32)[:, None], (N, n))
    return st, {"x": x, "y": y}


def train_flops_per_sample(model):
    return 6 * (model["d_in"] + model["num_classes"]) * model["d_hidden"]


def tiny(cfg):
    return cfg
'''

TOY_REFERENCE = '''
"""Plain reference of one SFPL round over the two-layer dense split: the
pooled mean loss of every client's rows, one SGD-momentum step of client
and server parts on its gradient a local step, the clients averaged at
the end."""
import functools

import jax
import jax.numpy as jnp
from jax import lax


def loss_of(cp, sp, x, y):
    a = jax.nn.relu(jnp.einsum("nbd,ndh->nbh", x, cp["w"])
                    + cp["b"][:, None])
    logits = a.reshape(-1, a.shape[-1]) @ sp["w"] + sp["b"]
    lse = jax.nn.logsumexp(logits, axis=-1)
    return jnp.mean(lse - jnp.take_along_axis(
        logits, y.reshape(-1, 1), axis=-1)[:, 0])


@functools.partial(jax.jit, static_argnames=("model", "fleet", "opt",
                                             "mode"))
def sfpl_round(params, data, *, model, fleet, opt, mode="f32"):
    fleet, opt = dict(fleet), dict(opt)
    B = fleet["per_client_batch"]

    def sgd(p, mu, g):
        g = g + opt["weight_decay"] * p
        mu = opt["momentum"] * mu + g
        return p - opt["lr"] * mu, mu

    def step(carry, t):
        p, mu = carry
        xb = lax.dynamic_slice_in_dim(data["x"], t * B, B, axis=1)
        yb = lax.dynamic_slice_in_dim(data["y"], t * B, B, axis=1)
        loss, g = jax.value_and_grad(
            lambda q: loss_of(q["cp"], q["sp"], xb, yb))(p)
        out = jax.tree_util.tree_map(sgd, p, mu, g)
        pick = lambda i: jax.tree_util.tree_map(
            lambda t: t[i], out, is_leaf=lambda t: isinstance(t, tuple))
        norms = jnp.stack([jnp.linalg.norm(a)
                           for a in jax.tree_util.tree_leaves(g)])
        return (pick(0), pick(1)), (loss, norms)

    zeros = jax.tree_util.tree_map(jnp.zeros_like, params)
    (p, _), (losses, norms) = lax.scan(
        step, (params, zeros), jnp.arange(fleet["steps_per_round"]))
    cp = jax.tree_util.tree_map(lambda a: jnp.broadcast_to(
        jnp.mean(a, axis=0, keepdims=True), a.shape), p["cp"])
    return losses, {"cp": cp, "sp": p["sp"]}, norms[0]
'''

TOY_CONFIG = {
    "name": "toy_dense", "reference": "toy_dense_ref.py",
    "family": "toy_dense",
    "model": {"d_in": 8, "d_hidden": 16, "num_classes": 4},
    "fleet": {"num_clients": 4, "classes_per_client": 1,
              "per_client_batch": 4, "steps_per_round": 4},
    "optimizer": {"kind": "sgd_momentum", "lr": 0.1, "momentum": 0.9,
                  "weight_decay": 5e-4},
}
TOY_LIMITS = {"loss0": 1e-5, "loss1": 1e-5, "dparam": 1e-3,
              "dparam_client": 1e-3}


@pytest.fixture
def toy_cell(tmp_path, monkeypatch):
    """The toy cell, its files written under ``tmp_path``, which ``spec``
    looks in instead of the benchmark's own directory."""
    files = {
        "families/toy_dense.py": textwrap.dedent(TOY_FAMILY),
        "configs/toy_dense_ref.py": textwrap.dedent(TOY_REFERENCE),
        "configs/toy_dense.json": json.dumps(TOY_CONFIG),
        "workloads/toy.f32.json": json.dumps({"limits": TOY_LIMITS}),
        "traffic/single_f32_highest.json":
            (S.HERE / "traffic" / "single_f32_highest.json").read_text(),
    }
    for rel, text in files.items():
        (tmp_path / rel).parent.mkdir(parents=True, exist_ok=True)
        (tmp_path / rel).write_text(text)
    bench = S.benchmark()
    bench["workloads"] = [{"name": "toy.f32", "config": "toy_dense",
                           "traffic": "single_f32_highest", "chips": 1}]
    monkeypatch.setattr(S, "HERE", tmp_path)
    return S.cell("toy.f32", bench)


@pytest.mark.parametrize("fault", [None, "misroute"])
def test_a_second_family_is_new_files_only(toy_cell, fault):
    fam = S.family(toy_cell["config"])
    if fault is None:
        res = R.run_cell(toy_cell, SEED, 0.05, configure_jax=False)
        assert res["correct"], res["checks"]
        assert res["attempted"] >= 1 and res["failed"] == 0
        assert set(res["metrics"]) >= {"samples_per_s", "setup_s"}
    else:
        with faults.planted(fault, fam):
            res = R.run_cell(toy_cell, SEED, 0.05, configure_jax=False)
        assert not res["correct"], res["checks"]
    assert set(res["checks"]) == set(TOY_LIMITS)
