"""Shared model utilities: loss, config base, remat/scan helpers."""
from __future__ import annotations

import dataclasses
import logging
import os
from typing import Optional, Tuple

import jax
import jax.numpy as jnp

logger = logging.getLogger(__name__)

IGNORE_LABEL = -100

# Read once at import time (an explicit keyword default), NOT inside the
# traced loss body — an env mutation between traces must not silently
# change an already-compiled graph's chunking.
DEFAULT_CE_CHUNKS = int(os.environ.get("REPRO_CE_CHUNKS", "8"))


@dataclasses.dataclass(frozen=True)
class ComputePolicy:
    """Mixed-precision policy for the split-model compute path.

    ``compute_dtype`` sets the matmul/conv/elementwise dtype for the
    client forward and the server forward-backward; master params, the
    BatchNorm statistics (batch AND running — the paper's CMSD/RMSD local
    inference policies need exact f32 moments), and the loss accumulation
    always stay f32.  With a non-f32 compute dtype the smashed-data
    exchange also travels the collector's ``all_to_all`` in that dtype —
    half the payload bytes for bf16.

    ``wire_dtype`` narrows the exchange payload INDEPENDENTLY of the
    compute dtype (``core.wire.WIRE_DTYPE_NAMES``): the smashed rows are
    quantized/cast immediately before each collective and restored to the
    compute dtype immediately after, so f32 compute with an int8 wire is
    a valid (and the paper-relevant constrained-uplink) configuration.
    ``wire_dtype_bwd`` does the same for the routed-back gradient rows —
    separate because the backward leg is usually the more
    quantization-sensitive one (default ``None`` = exact).

    ``use_fused_kernels`` follows the repo-wide ``None`` = auto-on-TPU
    convention and gates the fused Pallas ``bn_act`` / ``softmax_xent``
    epilogues; forced on off-TPU they run in Pallas interpret mode
    (``kernels.platform.interpret``).
    """
    compute_dtype: str = "float32"
    use_fused_kernels: Optional[bool] = None
    wire_dtype: Optional[str] = None
    wire_dtype_bwd: Optional[str] = None

    def cdtype(self):
        return jnp.dtype(self.compute_dtype)

    @property
    def mixed(self) -> bool:
        return self.cdtype() != jnp.float32

    def cast(self, x):
        """Cast an activation into the compute dtype (no-op at f32)."""
        return x.astype(self.cdtype()) if self.mixed else x

    def fused(self) -> bool:
        from repro.kernels.platform import auto_use_kernel
        return auto_use_kernel(self.use_fused_kernels)


def softmax_cross_entropy(logits, labels, *, ignore=IGNORE_LABEL,
                          z_loss_coef: float = 0.0):
    """logits: (..., V) ; labels: (...,) int32. Mean over non-ignored."""
    logits = logits.astype(jnp.float32)
    valid = labels != ignore
    safe = jnp.where(valid, labels, 0)
    lse = jax.nn.logsumexp(logits, axis=-1)
    ll = jnp.take_along_axis(logits, safe[..., None], axis=-1)[..., 0]
    loss = lse - ll
    if z_loss_coef:
        loss = loss + z_loss_coef * lse ** 2
    loss = jnp.where(valid, loss, 0.0)
    denom = jnp.maximum(jnp.sum(valid), 1)
    return jnp.sum(loss) / denom


def chunked_lm_loss(hidden, labels, unembed_fn, *, chunks=DEFAULT_CE_CHUNKS,
                    ignore=IGNORE_LABEL):
    """Cross-entropy over a large vocab without materializing full logits.

    ``hidden``: (B, S, d) final-norm output; ``unembed_fn(x) -> logits``.
    The sequence axis is split into ``chunks`` (default
    ``DEFAULT_CE_CHUNKS``, the ``REPRO_CE_CHUNKS`` env value at import
    time); each chunk's logits + loss are wrapped in jax.checkpoint, so
    the backward recomputes one chunk's logits at a time — peak logits
    memory drops by ~``chunks``x. This is a beyond-paper memory
    optimization recorded in EXPERIMENTS.md §Perf.
    """
    if chunks is None:
        chunks = DEFAULT_CE_CHUNKS
    B, S, d = hidden.shape
    requested = chunks
    while chunks > 1 and S % chunks != 0:
        chunks -= 1
    if chunks != requested:
        logger.warning(
            "chunked_lm_loss: seq len %d not divisible by chunks=%d; "
            "reduced to %d", S, requested, chunks)

    def one(xc, lc):
        logits = unembed_fn(xc).astype(jnp.float32)
        valid = lc != ignore
        safe = jnp.where(valid, lc, 0)
        lse = jax.nn.logsumexp(logits, axis=-1)
        ll = jnp.take_along_axis(logits, safe[..., None], axis=-1)[..., 0]
        loss = jnp.where(valid, lse - ll, 0.0)
        return jnp.sum(loss), jnp.sum(valid)

    one = jax.checkpoint(one)
    Sc = S // chunks
    total = jnp.zeros((), jnp.float32)
    count = jnp.zeros((), jnp.int32)
    for i in range(chunks):
        t, c = one(hidden[:, i * Sc:(i + 1) * Sc],
                   labels[:, i * Sc:(i + 1) * Sc])
        total = total + t
        count = count + c
    return total / jnp.maximum(count, 1)


def accuracy_from_logits(logits, labels, *, ignore=IGNORE_LABEL):
    valid = labels != ignore
    pred = jnp.argmax(logits, axis=-1)
    hit = (pred == labels) & valid
    return jnp.sum(hit) / jnp.maximum(jnp.sum(valid), 1)


@dataclasses.dataclass(frozen=True)
class TransformerConfig:
    """One config covers dense / GQA / MoE / VLM decoder variants."""
    name: str = "transformer"
    num_layers: int = 2
    d_model: int = 256
    num_heads: int = 4
    num_kv_heads: int = 4
    head_dim: int = 64
    d_ff: int = 512
    vocab_size: int = 1024
    act: str = "silu"                       # "gelu" -> GeGLU
    qk_norm: bool = False
    attn_bias: bool = False
    rope_theta: float = 10000.0
    mrope_sections: Optional[Tuple[int, int, int]] = None  # qwen2-vl
    norm_eps: float = 1e-6
    norm_scale_offset: float = 0.0          # gemma: 1.0  ((1+scale) rmsnorm)
    embed_scale: bool = False               # gemma: x *= sqrt(d_model)
    tie_embeddings: bool = True
    logit_softcap: float = 0.0              # gemma-2 style; 0 = off
    # MoE
    num_experts: int = 0
    d_ff_dense: int = 0                     # llama4 dense-layer MLP; 0=d_ff
    moe_layer_period: int = 1               # maverick: 2 (alternate layers)
    moe_shared_expert: bool = True
    capacity_factor: float = 1.25
    router_aux_coef: float = 0.01
    # attention pattern (llama4 iRoPE: 3 local chunked + 1 global)
    sliding_window: Optional[int] = None
    global_attn_period: int = 0             # 0 = all layers same window
    # execution
    param_dtype: str = "float32"
    compute_dtype: str = "bfloat16"
    attention_impl: str = "xla"
    remat: bool = True
    scan_layers: bool = True
    # activation sharding: ((axis, size), ...) or None (single device).
    # When set, residual-stream activations are sequence-sharded over the
    # "model" axis (Megatron sequence parallelism) and logits are
    # vocab-sharded — both essential to fit 16 GB/chip at 1M-token batches.
    mesh_axes: Optional[Tuple[Tuple[str, int], ...]] = None
    # vlm stub frontend
    vision_tokens: int = 0                  # >0 -> expects vision_embeds input

    @property
    def group_size(self) -> int:
        """Layers per scan group (lcm of layer-pattern periods)."""
        g = 1
        if self.num_experts and self.moe_layer_period > 1:
            g = _lcm(g, self.moe_layer_period)
        if self.global_attn_period:
            g = _lcm(g, self.global_attn_period)
        return g

    def layer_kind(self, idx: int) -> dict:
        """Static description of layer ``idx``'s flavour."""
        is_moe = bool(self.num_experts) and (
            (idx + 1) % max(self.moe_layer_period, 1) == 0)
        if self.global_attn_period:
            is_global = (idx + 1) % self.global_attn_period == 0
            window = None if is_global else self.sliding_window
        else:
            window = self.sliding_window
        return {"moe": is_moe, "window": window}

    def pdtype(self):
        return jnp.dtype(self.param_dtype)

    def cdtype(self):
        return jnp.dtype(self.compute_dtype)


def _lcm(a, b):
    import math
    return a * b // math.gcd(a, b)


def maybe_remat(fn, enabled):
    if not enabled:
        return fn
    return jax.checkpoint(fn, policy=jax.checkpoint_policies.nothing_saveable)


def constrain_dims(x, mesh_axes, roles):
    """Generic per-dim sharding constraint. roles: tuple of 'dp'|'tp'|None
    per dim (guarded by divisibility; no-op without mesh_axes)."""
    if not mesh_axes:
        return x
    from jax.sharding import PartitionSpec as P
    sizes = dict(mesh_axes)
    dp = tuple(a for a, _ in mesh_axes if a != "model")
    dp_size = 1
    for a in dp:
        dp_size *= sizes[a]
    tp = sizes.get("model", 1)
    spec = []
    for role, dim in zip(roles, x.shape):
        if role == "dp" and dim % dp_size == 0 and dim >= dp_size:
            spec.append(dp)
        elif role == "tp" and dim % tp == 0 and dim >= tp:
            spec.append("model")
        else:
            spec.append(None)
    return jax.lax.with_sharding_constraint(x, P(*spec))


def constrain_act(x, cfg, kind="residual"):
    """Sharding constraints on activations (no-op when cfg.mesh_axes unset
    or when a dim is not divisible by the assigned axis).

    kinds: "residual" (B,S,d) -> (dp, "model", None)   [sequence parallel]
           "logits"   (B,S,V) -> (dp, None, "model")   [vocab sharded]
    """
    axes = getattr(cfg, "mesh_axes", None)
    if not axes:
        return x
    from jax.sharding import PartitionSpec as P
    sizes = dict(axes)
    dp = tuple(a for a, _ in axes if a != "model")
    dp_size = 1
    for a in dp:
        dp_size *= sizes[a]
    tp = sizes.get("model", 1)

    b_ok = x.shape[0] % dp_size == 0 and x.shape[0] >= dp_size
    spec = [dp if b_ok else None, None, None]
    if kind == "residual":
        if x.shape[1] % tp == 0 and x.shape[1] >= tp:
            spec[1] = "model"
        elif not b_ok and x.shape[1] % (dp_size * tp) == 0:
            # batch=1 long-context: shard the sequence over everything
            spec[1] = dp + ("model",)
    elif kind == "logits":
        if x.shape[-1] % tp == 0:
            spec[-1] = "model"
        if not b_ok and x.shape[1] % dp_size == 0 and x.shape[1] >= dp_size:
            spec[1] = dp
    return jax.lax.with_sharding_constraint(x, P(*spec))


def count_params(params):
    return sum(int(x.size) for x in jax.tree_util.tree_leaves(params))
