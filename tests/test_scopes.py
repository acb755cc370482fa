"""The SFPL round names its phases and layer kinds in the compiled program.

``core.round.sfpl_round`` wraps each phase of a step in a sibling
``jax.named_scope("sfpl.<phase>")`` and ``nn`` wraps its convs and batch
norms in ``conv`` and ``bn``; the names reach the optimized HLO as
``metadata={op_name=...}``, which the chip benchmark's scope reduction
(``benchmarks/chip/scopes.py``) reads to attribute device time. Here the
small paper epoch (ResNet-8) is compiled on the CPU and its HLO read with
that reduction's own ``scope_map``.
"""
import re
import sys
from pathlib import Path

import pytest

from repro.launch import train as T

BENCH = Path(__file__).resolve().parents[1] / "benchmarks"
# not compute: they move no data on the device
NOT_COMPUTE = {"parameter", "tuple", "get-tuple-element", "constant",
               "bitcast"}
_HEADER = re.compile(r"^(ENTRY\s+)?%?([\w.\-]+)\s.*\{\s*$")
_NAME = re.compile(r"^\s*(?:ROOT\s+)?%([\w.\-]+)\s*=\s*")


@pytest.fixture(scope="module")
def SC():
    if str(BENCH) not in sys.path:
        sys.path.insert(0, str(BENCH))
    from chip import scopes
    return scopes


def opcode(rest):
    """The opcode of an instruction line's right-hand side (its shape
    skipped, a tuple shape included)."""
    if rest.startswith("("):
        depth = 0
        for i, ch in enumerate(rest):
            depth += (ch == "(") - (ch == ")")
            if depth == 0:
                break
        rest = rest[i + 1:]
    else:
        rest = rest.split(" ", 1)[1]
    return re.match(r"\s*([\w\-]+)\(", rest).group(1)


def computations(text):
    """({computation: [(instruction, opcode, line)]}, entry name)."""
    comps, cur, entry = {}, None, None
    for line in text.splitlines():
        h = _HEADER.match(line)
        if h and not line.startswith(" "):
            cur = h.group(2)
            comps[cur] = []
            entry = cur if h.group(1) else entry
        elif line.startswith("}"):
            cur = None
        elif cur is not None and (m := _NAME.match(line)):
            comps[cur].append((m.group(1), opcode(line[m.end():]), line))
    return comps, entry


@pytest.fixture(scope="module")
def hlo():
    run = T.build_paper(num_clients=4, batch_size=4)
    return run.epoch.jitted.lower(run.key, run.st, run.data).compile() \
        .as_text()


def test_every_step_op_carries_one_phase(hlo, SC):
    smap = SC.scope_map(hlo)
    comps, entry = computations(hlo)
    # the scan over the local steps: the largest loop outside any phase
    loops = [re.search(r"body=%?([\w.\-]+)", line).group(1)
             for ops in comps.values() for name, op, line in ops
             if op == "while" and SC.phase_of(smap.get(name, "")) is None]
    body = max(loops, key=lambda b: len(comps[b]))
    compute = [n for n, op, _ in comps[body] if op not in NOT_COMPUTE]
    phases = [SC.phase_of(smap.get(n, "")) for n in compute]
    scoped = sum(p is not None for p in phases)
    assert len(compute) > 50
    assert scoped >= 0.95 * len(compute), (
        scoped, len(compute),
        [n for n, p in zip(compute, phases) if p is None])
    got = set(phases)
    # the server forward and its transpose, the backward autodiff emits
    assert ("server", "fwd") in got and ("server", "bwd") in got
    for p in ("client_fwd", "shuffle", "server_opt", "client_update"):
        assert any(g and g[0] == p for g in got), p
    kinds = {SC.kind_of(op) for op in smap.values()}
    assert {"conv", "bn"} <= kinds
    # FedAvg runs once a round, after the loop
    assert "fedavg" not in {p[0] for p in phases if p}
    assert any(SC.phase_of(smap.get(n, "")) == ("fedavg", "fwd")
               for n, _, _ in comps[entry])
