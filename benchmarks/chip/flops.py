"""Operations and bytes of the SFPL round that every family shares, and
the chip's peaks.

A family (``families/<family>.py``) counts its own model from the
configuration's published sizes (``train_flops_per_sample`` and its
kernels' costs); nothing here reads the program.

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


def pool_rows(fleet):
    return fleet["num_clients"] * fleet["per_client_batch"]


def permute_cost(row_elems, fleet, chips, wire_dtype):
    """(ops, bytes) per chip of one round's bucket/unbucket permutes: four
    kernels a step (forward and backward, bucket and unbucket), each
    reading and writing the chip's share of the pool once; a row holds
    ``row_elems`` elements (the family's ``smashed_elems``)."""
    row = row_elems * DTYPE_BYTES[wire_dtype]
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
