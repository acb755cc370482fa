"""Device time by the program's own ``jax.named_scope``s.

The round's executable names each HLO instruction's scope path in its
``metadata={op_name=...}``. A scope map (``from_text(compiled.as_text())``)
keeps that path by instruction name; ``phase_of`` and ``kind_of`` read the
round's phase (``sfpl.<phase>``) and the layer kind (``conv``, ``bn``)
from it. A TPU trace names each device op by its whole instruction text,
so ``scope_ns`` can sum the device time of ``trace.load``'s ops by scope.

A per-layer reader gets the map through ``of_ctx``: the first reader of a
traced run builds the cell's round again through the launcher, with the
harness's own arguments, and takes the map from its compiled executable,
which is the one the window ran (the same program, loaded from the
compile cache). This runs after the window, so neither the window nor
set-up moves, and an untraced run does none of it. Where the program
names no ``sfpl.`` scope (a program without them), the readers read
nothing.
"""
from __future__ import annotations

import json
import re
import sys
import time
import traceback

from chip import trace as TR

_MODULE = re.compile(r"^HloModule\s+([^\s,]+)", re.M)
_INSTR = re.compile(r"^\s*(?:ROOT\s+)?%([\w.\-]+)\s*=\s(.*)$")
_OPERAND = re.compile(r"%([\w.\-]+)")
_OP_NAME = re.compile(r'\bop_name="([^"]*)"')
_WRAPPED = re.compile(r"^[\w.\-]*\((.*)\)$")
PHASE_PREFIX = "sfpl."
KINDS = ("conv", "bn")
_UNSET = object()


def log(*a):
    print(*a, file=sys.stderr, flush=True)


def instr_name(op):
    """``%fusion.12 = f32[8]{0} fusion(...)`` or ``fusion.12`` ->
    ``fusion.12``: the HLO instruction's full name, unique in its
    module."""
    return op.split(" = ", 1)[0].strip().lstrip("%")


def scope_map(hlo_text):
    """{instruction name: its ``metadata={op_name=...}``} of a compiled
    module's text (``compiled.as_text()``).

    An instruction that the compiler added without an ``op_name`` of its
    own (the asynchronous ``copy-start``/``copy-done`` of a prefetch or an
    eviction, a ``slice-start``) takes the ``op_name`` of its first
    consumer that has one, in schedule order, since it exists to feed that
    op; failing that (an eviction feeds only the loop's result tuple), the
    ``op_name`` of its first producer that has one. Instructions that
    reach neither are left out."""
    own, users, operands, order = {}, {}, {}, []
    for line in hlo_text.splitlines():
        m = _INSTR.match(line)
        if not m:
            continue
        name, rest = m.groups()
        head, _, meta = rest.partition(", metadata={")
        op = _OP_NAME.search(meta.split("}", 1)[0]) if meta else None
        if op:
            own[name] = op.group(1)
        order.append(name)
        operands[name] = _OPERAND.findall(head)
        for o in operands[name]:
            users.setdefault(o, []).append(name)
    out = dict(own)
    for links in (users, operands):
        found = {}

        def reach(name, seen):
            """The first ``op_name`` along ``links`` from ``name``."""
            if name in own:
                return own[name]
            if name in found or name in seen:
                return found.get(name)
            seen.add(name)
            for nxt in links.get(name, ()):
                op = reach(nxt, seen)
                if op is not None:
                    found[name] = op
                    return op
            return None

        for name in order:
            if name not in out and (op := reach(name, set())):
                out[name] = op
    return out


def module_name(hlo_text):
    """The module's name from its ``HloModule`` line, or ``None``."""
    m = _MODULE.search(hlo_text)
    return m.group(1) if m else None


def from_text(hlo_text):
    """What the reductions need of the round's executable: its module's
    name and its scope map."""
    return {"module": module_name(hlo_text), "ops": scope_map(hlo_text)}


def _components(op_name):
    """[(scope, transposed)] of the ``/``-components of an ``op_name``,
    each with the transformations that wrap it (``jvp(...)``,
    ``vmap(...)``, ``transpose(...)``) peeled off."""
    out = []
    for c in op_name.split("/"):
        name, transposed = c, False
        while (m := _WRAPPED.match(name)):
            transposed |= name.startswith("transpose(")
            name = m.group(1)
        out.append((name, transposed))
    return out


def phase_of(op_name):
    """``(phase, "fwd" | "bwd")`` of an op's ``op_name``: the one
    ``sfpl.<phase>`` scope among its components (autodiff may repeat it
    inside a transformation), backward where that scope or one inside it
    is transposed; ``None`` without one, or with two different phases."""
    comps = _components(op_name)
    idx = [i for i, (n, _) in enumerate(comps)
           if n.startswith(PHASE_PREFIX)]
    if len({comps[i][0] for i in idx}) != 1:
        return None
    bwd = any(t for _, t in comps[idx[0]:])
    return comps[idx[0]][0][len(PHASE_PREFIX):], "bwd" if bwd else "fwd"


def kind_of(op_name):
    """The innermost layer kind (``conv`` or ``bn``) among an op's
    ``op_name`` components, or ``None``."""
    for name, _ in reversed(_components(op_name)):
        if name in KINDS:
            return name
    return None


def scope_ns(tr, lo, hi, scopes):
    """Device time by scope, averaged over the devices: ``{(phase,
    direction, kind, base name): ns}`` over every op that started in the
    window. ``phase`` and ``direction`` are ``None`` for an op without a
    phase (one the map lacks among them), ``kind`` for one outside a
    ``conv`` or ``bn``. Durations are summed, not merged; control flow is
    left out."""
    out, keys = {}, {}
    for ev in tr["devices"].values():
        for n, s, d in ev:
            if not lo <= s < hi:
                continue
            # a trace repeats each instruction once a round: key it once
            key = keys.get(n)
            if key is None:
                b = TR.base_name(n)
                op = scopes["ops"].get(instr_name(n), "")
                key = keys[n] = ((*(phase_of(op) or (None, None)),
                                  kind_of(op), b) if TR.is_op(b) else ())
            if key:
                out[key] = out.get(key, 0.0) + d
    nd = max(len(tr["devices"]), 1)
    return {k: v / nd for k, v in out.items()}


def idle_gaps(tr, lo, hi, scopes, k=10):
    """``trace.idle_gaps``, each label with ``@sfpl.<phase>`` of the op
    that ends the gap appended (``@none`` where that op has no phase or
    no op ends it)."""
    if not tr["devices"]:
        return []
    first = sorted(tr["devices"])[0]
    busy = TR.intervals(tr["devices"][first], lo, hi)
    gaps, cur = [], lo
    for a, b in busy:
        if a > cur:
            gaps.append((cur, a))
        cur = max(cur, b)
    if cur < hi:
        gaps.append((cur, hi))
    gaps = sorted(gaps, key=lambda g: g[0] - g[1])[:k]
    ends, starts = {b for _, b in gaps}, {}
    for n, s, _ in tr["devices"][first]:
        if s in ends and TR.is_op(TR.base_name(n)):
            starts.setdefault(s, n)
    out = []
    for a, b in gaps:
        mid = (a + b) / 2
        open_ = [(s, n) for n, s, d in tr["spans"]
                 if s <= mid < s + d and n != "bench.window"]
        label = max(open_)[1] if open_ else "host.none"
        phase = (phase_of(scopes["ops"].get(instr_name(starts[b]), ""))
                 if b in starts else None)
        label += "@" + (PHASE_PREFIX + phase[0] if phase else "none")
        out.append([label, (b - a) / 1e9])
    return out


def round_text(config, traffic):
    """The optimized HLO text of a cell's round as the window runs it:
    built through the launcher with the harness's arguments, lowered on
    state and data placed in the launcher's layout (committed, as
    ``run.Program`` places them, so that the module and its compile-cache
    key are the window's) and a key of the window's type, and compiled
    (loaded from the compile cache)."""
    import jax
    import numpy as np

    from chip import gen
    from chip import run as R
    from repro.launch import train as T
    run = T.build_paper(**R.build_kwargs(config, traffic))
    place = lambda t: jax.device_put(
        t, jax.tree_util.tree_map(lambda a: a.sharding, t))
    key = np.asarray(jax.random.split(gen.seed_key(0), 2))[0]
    text = run.epoch.jitted.lower(key, place(run.st), place(run.data)) \
        .compile().as_text()
    del run
    return text


def of_ctx(ctx):
    """The scope map of a reader context's round, made once a run (the
    context keeps it as ``scopes``; a test may set it there, ``None``
    meaning no map), or ``None`` where it cannot be made."""
    if getattr(ctx, "scopes", _UNSET) is _UNSET:
        t = time.perf_counter()
        try:
            ctx.scopes = from_text(round_text(ctx.config, ctx.traffic))
        except Exception:                       # a reader never raises
            log("scope map: not made\n" + traceback.format_exc())
            ctx.scopes = None
        else:
            log(f"scope map: {len(ctx.scopes['ops'])} instructions of "
                f"module {ctx.scopes['module']} in "
                f"{time.perf_counter() - t!r} s")
            log_breakdown(ctx)
    return ctx.scopes


def ns_of(ctx):
    """``scope_ns`` of the context's window, once a run, or ``None``
    without a scope map."""
    scopes = of_ctx(ctx)
    if scopes is None:
        return None
    if getattr(ctx, "scope_ns", None) is None:
        ctx.scope_ns = scope_ns(ctx.tr, ctx.lo, ctx.hi, scopes)
    return ctx.scope_ns


def _scoped(ctx, keep):
    """Summed ns of the window's ops whose ``(phase, direction, kind)``
    ``keep`` accepts, or ``None`` where no op is such (no scope map, or a
    program without those scopes)."""
    got = ns_of(ctx)
    if got is None:
        return None
    ns = [v for (p, d, k, _), v in got.items() if keep(p, d, k)]
    return sum(ns) if ns else None


def phase_ms(ctx, *phases):
    """Device ms per round of the ops in the given ``(phase, direction)``
    pairs (a direction of ``None`` takes both)."""
    ns = _scoped(ctx, lambda p, d, _: (p, d) in phases
                 or (p, None) in phases)
    return None if ns is None else ns / 1e6 / ctx.rounds_traced


def kind_share(ctx, kind):
    """Share of device busy time, in %, of the ops inside the layer kind
    ``kind``."""
    ns, busy = _scoped(ctx, lambda p, d, k: k == kind), ctx.busy_ns()
    if ns is None or not busy:
        return None
    return 100.0 * ns / (sum(busy.values()) / len(busy))


def log_breakdown(ctx):
    """On stderr: device ms per round by phase, direction and kind, of
    the ops without a phase by op, of the five ops with most time by
    scope, and the longest idle gaps with the phase that ends each."""
    by_scope, rounds = ns_of(ctx), ctx.rounds_traced

    def tally(key):
        out = {}
        for k, v in by_scope.items():
            if key(k) is not None:
                out[key(k)] = out.get(key(k), 0.0) + v / 1e6 / rounds
        return dict(sorted(out.items(), key=lambda x: -x[1]))

    log("device ms per round by phase/direction/kind: " + json.dumps(
        tally(lambda k: "/".join(map(str, k[:3])))))
    log("device ms per round of the ops without a phase: " + json.dumps(
        tally(lambda k: k[3] if k[0] is None else None)))
    top = list(tally(lambda k: k[3]))[:5]
    log("device ms per round of the top ops by scope: " + json.dumps(
        {b: tally(lambda k: "/".join(map(str, k[:3])) if k[3] == b
                  else None) for b in top}))
    log("idle gaps by the phase that ends them: " + json.dumps(
        idle_gaps(ctx.tr, ctx.lo, ctx.hi, ctx.scopes)))
