"""Ahead-of-time compiles for a described TPU v5e (2x2 topology).

The TPU compiler is installed with jax, and it compiles for a chip that is
described, not attached. These tests compile the main path's six Pallas
kernels at real width (16,384-feature smashed rows: 32x32x16), forward and
grad, and one jitted sharded SFPL epoch (ResNet-32, width 16) on a 4-chip
mesh, from shapes alone. They catch what interpret mode cannot: block
shapes the TPU lowering refuses, VMEM overflow, an epoch that does not fit
the chip's memory, and a kernel that silently fell back to its reference
path (no ``tpu_custom_call``). They say nothing about results or times.

The topology is described inside a module fixture, never at import time:
only one process at a time may load the TPU library, and every test worker
imports this file. Code that asks ``jax.default_backend()`` still sees the
CPU here, so the ``tpu`` fixture steers the repo's one platform decision
(``kernels.platform.on_tpu``) for the duration of a test.
"""
import jax
import jax.numpy as jnp
import pytest
from jax.sharding import NamedSharding, PartitionSpec as P
from jax.sharding import SingleDeviceSharding

from repro.core import engine as E
from repro.core import engine_dist as ED
from repro.core import round as RD
from repro.configs.resnet_cifar import MODELS
from repro.kernels import platform
from repro.kernels.bn_act.ops import bn_act
from repro.kernels.collector_permute.ops import (bucket_permute_ad,
                                                 unbucket_permute_ad)
from repro.kernels.quant_permute.ops import (dequant_unbucket_permute,
                                             quant_bucket_permute,
                                             quant_dequant_roundtrip_ad)
from repro.kernels.softmax_xent.ops import softmax_xent
from repro.models import resnet as R
from repro.optim import sgd_momentum
from repro.roofline.hlo import pallas_kernel_counts

ROWS, FEAT = 160, (32, 32, 16)     # one shard's slab of a 640-row pool
S, CAP = 4, 40                     # 4 destination shards x 40 slots
F32, BF16 = jnp.float32, jnp.bfloat16


@pytest.fixture(scope="module")
def topo():
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache as cc
    # a compile for a described chip can be written to the persistent
    # cache but not read back without one: keep the cache off meanwhile
    cache_on = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    cc.reset_cache()
    try:
        with pytest.MonkeyPatch.context() as mp:
            mp.setenv("TPU_LOG_DIR", "disabled")
            try:
                desc = topologies.get_topology_desc(
                    platform="tpu", topology_name="v5e:2x2")
            except Exception as e:  # noqa: BLE001 — no TPU compiler here
                pytest.skip(f"no v5e:2x2 topology can be described: {e}")
            yield desc
    finally:
        jax.config.update("jax_enable_compilation_cache", cache_on)
        cc.reset_cache()


@pytest.fixture
def tpu(topo, monkeypatch):
    """One-chip sharding on the described topology, with the kernels'
    platform decision answering TPU (compiled, not interpreted)."""
    monkeypatch.setattr(platform, "on_tpu", lambda: True)
    return SingleDeviceSharding(topo.devices[0])


def _sds(shape, dtype, sharding):
    return jax.ShapeDtypeStruct(shape, dtype, sharding=sharding)


def _kernels(fn, *args):
    return pallas_kernel_counts(jax.jit(fn).lower(*args).compile().as_text())


def _sum(y):
    return jnp.sum(y.astype(F32))


# (kernel name, build(one_chip, dtype) -> (fn, args)); each runs forward
# and under value_and_grad, so the kernel must survive autodiff too
def _bucket(one, dt):
    return (lambda x, i: bucket_permute_ad(x, i),
            (_sds((ROWS,) + FEAT, dt, one), _sds((S, CAP), jnp.int32, one)))


def _unbucket(one, dt):
    return (lambda x, i: unbucket_permute_ad(x, i),
            (_sds((ROWS,) + FEAT, dt, one), _sds((ROWS,), jnp.int32, one)))


def _quant(one, dt):
    return (lambda x, s, r: quant_dequant_roundtrip_ad(x, s, r, "int8"),
            (_sds((ROWS,) + FEAT, dt, one), _sds((S, CAP), jnp.int32, one),
             _sds((ROWS,), jnp.int32, one)))


def _bn_act(one, dt):
    return (lambda x, a, b: bn_act(x, a, b),
            (_sds((ROWS,) + FEAT, dt, one), _sds((FEAT[-1],), F32, one),
             _sds((FEAT[-1],), F32, one)))


def _xent(one, dt):
    return (lambda z, y: softmax_xent(z, y),
            (_sds((640, 100), dt, one), _sds((640,), jnp.int32, one)))


CASES = {
    "sfpl_bucket_permute": _bucket,
    "sfpl_unbucket_permute": _unbucket,
    "sfpl_quant_bucket_permute": _quant,
    "sfpl_dequant_unbucket_permute": _quant,
    "sfpl_bn_act": _bn_act,
    "sfpl_xent_fwd": _xent,
}


@pytest.mark.parametrize("grad", [False, True], ids=["fwd", "grad"])
@pytest.mark.parametrize("dtype", [F32, BF16], ids=["f32", "bf16"])
@pytest.mark.parametrize("kernel", sorted(CASES))
def test_kernel_compiles_for_v5e(tpu, kernel, dtype, grad):
    fn, args = CASES[kernel](tpu, dtype)
    if grad:
        f = fn
        fn = lambda x, *rest: jax.value_and_grad(
            lambda v: _sum(f(v, *rest)))(x)
    counts = _kernels(fn, *args)
    assert counts.get(kernel), counts
    if kernel == "sfpl_xent_fwd" and grad:
        assert counts.get("sfpl_xent_bwd"), counts


@pytest.mark.parametrize("wire", ["int8", "float8_e4m3"])
def test_quantized_wire_kernels_compile_per_side(tpu, wire):
    """The send and receive halves of a quantized exchange compile on
    their own, in the wire dtype the all_to_all carries."""
    x = _sds((ROWS,) + FEAT, F32, tpu)
    idx = _sds((S, CAP), jnp.int32, tpu)
    send = _kernels(lambda x, i: quant_bucket_permute(x, i, wire_dtype=wire),
                    x, idx)
    assert send.get("sfpl_quant_bucket_permute"), send
    q = _sds((ROWS, 16384), jnp.dtype(
        {"int8": jnp.int8, "float8_e4m3": jnp.float8_e4m3fn}[wire]), tpu)
    recv = _kernels(lambda q, s, i: dequant_unbucket_permute(
        q, s, i, out_dtype=BF16), q, _sds((ROWS,), F32, tpu),
        _sds((ROWS,), jnp.int32, tpu))
    assert recv.get("sfpl_dequant_unbucket_permute"), recv


def test_sharded_sfpl_epoch_compiles_on_four_chips(topo, tpu):
    """A jitted sharded SFPL epoch at ResNet-32 width 16 (8 single-class
    clients, per-client batch 64) compiles for a 4-chip v5e mesh with the
    collector kernels and the all-to-all exchange in it, and a per-chip
    footprint inside the chip's 16 GB."""
    n, b = 8, 64
    cfg = MODELS["resnet32"](num_classes=n)
    split = E.make_resnet_split(cfg)
    opt = sgd_momentum(0.05, momentum=0.9, weight_decay=5e-4)
    st = jax.eval_shape(lambda: E.init_dcml_state(
        jax.random.PRNGKey(0), lambda k: R.init(k, cfg), n, opt, opt))
    mesh = ED.make_auto_mesh((4,), ("data",), devices=topo.devices)
    shard = NamedSharding(mesh, P("data"))
    sh = RD.DataMesh(mesh, "data").state_shardings(st)
    st = {k: jax.tree_util.tree_map(
        lambda a, s=sh[k]: _sds(a.shape, a.dtype, s), v)
        for k, v in st.items()}
    data = {"x": _sds((n, b, 32, 32, 3), F32, shard),
            "y": _sds((n, b), jnp.int32, shard)}
    key = _sds((2,), jnp.uint32, NamedSharding(mesh, P()))
    epoch = jax.jit(lambda k, s, d: ED.sfpl_epoch_sharded(
        k, s, d, split, opt, opt, mesh=mesh, num_clients=n, batch_size=b),
        donate_argnums=(1,))
    compiled = epoch.lower(key, st, data).compile()
    text = compiled.as_text()
    counts = pallas_kernel_counts(text)
    assert counts.get("sfpl_bucket_permute"), counts
    assert counts.get("sfpl_unbucket_permute"), counts
    assert "all-to-all" in text
    mem = compiled.memory_analysis()
    per_chip = (mem.argument_size_in_bytes + mem.output_size_in_bytes
                + mem.temp_size_in_bytes - mem.alias_size_in_bytes)
    assert per_chip < 16e9, per_chip
