"""HLO-text parsing: collective traffic extraction for the roofline.

``cost_analysis()`` does not expose collective bytes, so we parse the
compiled module text and sum the result-shape sizes of every collective op.

Traffic model per op type (ring algorithms, n = participants; we report the
result-bytes and a traffic multiplier):
  all-gather         result is the gathered buffer; traffic/device ~ (n-1)/n
                     of result  -> factor 1.0 (upper bound)
  all-reduce         ~2x the buffer (reduce-scatter + all-gather phases)
  reduce-scatter     traffic ~ input ~ result * n ... we only see the result;
                     factor n/(n-1) ~ 1.0 of the *input*; we use result*1.0
                     (lower bound, flagged in EXPERIMENTS.md)
  all-to-all         each device sends (n-1)/n of its shard -> factor 1.0
  collective-permute ~1.0
"""
from __future__ import annotations

import re

_DTYPE_BYTES = {
    "pred": 1, "s8": 1, "u8": 1, "f8e4m3fn": 1, "f8e5m2": 1,
    "s16": 2, "u16": 2, "bf16": 2, "f16": 2,
    "s32": 4, "u32": 4, "f32": 4,
    "s64": 8, "u64": 8, "f64": 8, "c64": 8, "c128": 16,
}

_COLL_FACTOR = {
    "all-gather": 1.0,
    "all-reduce": 2.0,
    "reduce-scatter": 1.0,
    "all-to-all": 1.0,
    "collective-permute": 1.0,
}

# e.g.:  %all-gather.3 = bf16[4,512,1024]{2,1,0} all-gather(...)
# also tuple-shaped: (bf16[...], bf16[...]) all-reduce(...)
_SHAPE_RE = re.compile(r"([a-z0-9]+)\[([0-9,]*)\]")
_OP_RE = re.compile(
    r"=\s*(?:\([^)]*\)|[a-z0-9]+\[[0-9,]*\][^ ]*)\s*"
    r"(all-gather|all-reduce|reduce-scatter|all-to-all|collective-permute)"
    r"(?:-start)?\(")


def _line_result_bytes(line):
    # everything between '=' and the op name is the result shape(s)
    lhs = line.split("=", 1)[1]
    op_pos = len(lhs)
    m = re.search(
        r"(all-gather|all-reduce|reduce-scatter|all-to-all|"
        r"collective-permute)(-start|-done)?\(", lhs)
    if m:
        op_pos = m.start()
    total = 0
    for dt, dims in _SHAPE_RE.findall(lhs[:op_pos]):
        if dt not in _DTYPE_BYTES:
            continue
        n = 1
        for d in dims.split(","):
            if d:
                n *= int(d)
        total += n * _DTYPE_BYTES[dt]
    return total


def collective_bytes_from_text(hlo_text):
    """Returns {op_type: {"count": int, "bytes": int, "traffic_bytes": int}}.

    ``bytes`` is the summed result-shape size (per device, since the module
    is the SPMD-partitioned per-device program); ``traffic_bytes`` applies
    the per-op traffic factor.
    """
    out = {}
    for line in hlo_text.splitlines():
        m = _OP_RE.search(line)
        if not m:
            # handle "-done" lines? bytes counted at -start only
            continue
        if "-done(" in line:
            continue
        op = m.group(1)
        b = _line_result_bytes(line)
        d = out.setdefault(op, {"count": 0, "bytes": 0, "traffic_bytes": 0})
        d["count"] += 1
        d["bytes"] += b
        d["traffic_bytes"] += int(b * _COLL_FACTOR[op])
    return out


# e.g.:  %sfpl_bucket_permute.1 = f32[160,1,16384]{...} custom-call(...),
#        custom_call_target="tpu_custom_call", ...
_KERNEL_RE = re.compile(r"^\s*(?:ROOT\s+)?%?([\w\-]+?)(?:\.\d+)?\s*=.*"
                        r'custom_call_target="tpu_custom_call"')


def pallas_kernel_counts(hlo_text):
    """{kernel name: number of ``tpu_custom_call`` instructions} in a
    compiled TPU module's text. A Pallas kernel's instruction is named
    after its ``pallas_call(name=...)``, so a kernel that silently fell
    back to its reference path is simply absent."""
    counts = {}
    for line in hlo_text.splitlines():
        m = _KERNEL_RE.match(line)
        if m:
            counts[m.group(1)] = counts.get(m.group(1), 0) + 1
    return counts
