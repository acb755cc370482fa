"""Operations and bytes of the SFPL round, from shapes alone.

Everything here is arithmetic on a configuration's published sizes (the
``model`` and ``fleet`` groups of ``configs/<config>.json``) and a
traffic mix's dtypes; nothing reads the program.

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
* ``permute_cost`` — the bytes the collector's ``bucket_permute`` and
  ``unbucket_permute`` kernels must move in one round on each chip: each
  reads and writes every pool row of its chip once, forward (smashed rows)
  and backward (routed-back gradient rows).
"""
from __future__ import annotations

import json
from pathlib import Path

PEAKS = Path(__file__).resolve().parent / "peaks.json"

DTYPE_BYTES = {"float32": 4, "bfloat16": 2, "float16": 2, "int8": 1,
               "float8_e4m3fn": 1}


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


def pool_rows(fleet):
    return fleet["num_clients"] * fleet["per_client_batch"]


def bn_act_cost(model, fleet, compute_dtype):
    """(ops, bytes) of one round's BN+activation epilogues, all chips:
    each reads the conv output and writes the activation once in the
    compute dtype, and reads its f32 scale and shift vectors."""
    item = DTYPE_BYTES[compute_dtype]
    rows = pool_rows(fleet)
    ops = nbytes = 0
    for elems, chans, relu in bn_layers(model):
        n = rows * elems
        ops += n * (3 if relu else 2)
        nbytes += 2 * n * item + 2 * chans * 4
    steps = fleet["steps_per_round"]
    return ops * steps, nbytes * steps


def permute_cost(model, fleet, chips, wire_dtype):
    """(ops, bytes) per chip of one round's bucket/unbucket permutes: four
    kernels a step (forward and backward, bucket and unbucket), each
    reading and writing the chip's share of the pool once."""
    row = model["input_hw"] ** 2 * model["width"] * DTYPE_BYTES[wire_dtype]
    rows = pool_rows(fleet) // chips
    return 0, 4 * 2 * rows * row * fleet["steps_per_round"]


def least_time_s(ops, nbytes, peaks, flops_key="bf16_flops"):
    """(seconds, bound): the larger of the compute and the memory time."""
    t_ops = ops / peaks[flops_key]
    t_mem = nbytes / peaks["hbm_bytes_per_s"]
    return (t_ops, "compute") if t_ops >= t_mem else (t_mem, "memory")


def peaks(kind):
    """The published peaks of a device kind (``peaks.json``); a kind that
    is not there is an error, never a default."""
    table = json.loads(PEAKS.read_text())
    if kind not in table or kind.startswith("_"):
        raise KeyError(f"no peaks for device kind {kind!r} in {PEAKS.name}")
    return table[kind]
