"""One run of one cell of the SFPL chip benchmark.

    python3 benchmarks/chip/run.py --workload <cell> --seed <n> \
        --seconds <s> --trace <0|1>

Set-up (``setup_s``): build the cell's round through the program's own
launcher, as the configuration's family says (``families/<family>.py``
``build``), make the state and the client data on the device from
``--seed`` (the family's ``make_inputs``) and place them in the layout
the launcher chose, then drive the jitted round (``.epoch.jitted``: the
round's local steps and the FedAvg) twice. The first call compiles (or
loads from the compile cache) and is the round that the correctness check
compares; the second proves the window's call signature warm.

Window: rounds back to back for ``--seconds``, one ``block_until_ready``
per round, each round with its own key. ``--trace 1`` records the window
with the profiler and reports the per-layer metrics instead of the
end-to-end ones; after the window, the round's executable, lowered on the
live state and data, gives the scope map that the scope readers read
(``scopes.of_program``).

After the window: the device's peak memory is read, the program's state is
dropped, and the plain reference (``configs/<reference>``) recomputes the
first round from the same seed, in float32 at full precision. ``correct``
holds when each compared number is within its cell's limit
(``workloads/<cell>.json``); each is printed beside its limit as the last
lines of stderr and under the result's last key.

The last stdout line is one JSON object: ``correct``, ``attempted``
(rounds in the window), ``failed`` (rounds whose losses were not finite),
``metrics``, ``device``, with ``--trace 1`` also ``breakdown``, and
``checks``. Without a TPU, or with fewer chips than the cell needs, the
run exits non-zero before printing any result.
"""
from __future__ import annotations

import argparse
import contextlib
import gc
import json
import math
import shutil
import statistics
import sys
import time
from pathlib import Path

T_START = time.perf_counter()
HERE = Path(__file__).resolve().parent
# ``benchmarks/`` for the ``chip`` package, ``src/`` for the program; the
# script's own directory goes, so that ``trace`` is never taken for ours.
sys.path[:] = [p for p in sys.path if Path(p or ".").resolve() != HERE]
for p in (HERE.parents[1] / "src", HERE.parent):
    if str(p) not in sys.path:
        sys.path.insert(0, str(p))

import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402
import numpy as np  # noqa: E402

from chip import flops as F  # noqa: E402
from chip import gen  # noqa: E402
from chip import scopes as SC  # noqa: E402
from chip import spec as S  # noqa: E402
from chip import trace as TR  # noqa: E402

N_KEYS = 1 << 14
TRACE_DIR = HERE.parents[1] / ".bench_trace"
# leaves whose step-0 reference gradient is under this share of the
# median leaf's move by round-off alone and are left out of ``dparam``
ZERO_GRAD = 1e-3


def log(*a):
    print(*a, file=sys.stderr, flush=True)


def require_chips(chips):
    devs = jax.devices()
    if devs[0].platform != "tpu":
        raise SystemExit(f"no TPU: JAX found {devs[0].platform} devices; "
                         f"this benchmark measures only on the chip")
    if len(devs) < chips:
        raise SystemExit(f"the cell needs {chips} chips, JAX found "
                         f"{len(devs)}")


def _shapes(tree):
    return jax.tree_util.tree_map(lambda a: (a.shape, str(a.dtype)), tree)


def host_params(st):
    return jax.device_get({"cp": st["cp"], "sp": st["sp"]})


class Program:
    """The cell's jitted round, with its state and data placed in the
    launcher's layout (``start``) and warmed."""

    def __init__(self, cell, **build):
        """``build`` overrides the launcher's arguments (calibration's
        looks only)."""
        cfg, traffic = cell["config"], cell["traffic"]
        self.cfg, self.traffic = cfg, traffic
        self.family = S.family(cfg)
        self.phase_s = {"start": time.perf_counter() - T_START}
        t = time.perf_counter()
        with jax.profiler.TraceAnnotation("bench.build"):
            run = self.family.build(cfg, traffic, **build)
            self.st_layout = jax.tree_util.tree_map(lambda a: a.sharding,
                                                    run.st)
            self.data_layout = jax.tree_util.tree_map(lambda a: a.sharding,
                                                      run.data)
            self.want = _shapes(run.st)
            self.fn = run.epoch.jitted
            del run
        self.phase_s["build"] = time.perf_counter() - t

    def start(self, seed):
        """State and data from ``seed``; the first round (compiled here,
        or loaded from the cache, and kept for the correctness check) and
        a second one."""
        t = time.perf_counter()
        with jax.profiler.TraceAnnotation("bench.inputs"):
            key = gen.seed_key(seed)
            st, data = self.family.make_inputs(key, self.cfg)
            if _shapes(st) != self.want:
                raise SystemExit("the benchmark's state does not match the "
                                 f"program's layout: {_shapes(st)} != "
                                 f"{self.want}")
            self.st = jax.device_put(st, self.st_layout)
            self.data = jax.device_put(data, self.data_layout)
            del st, data
            self.keys = np.asarray(jax.random.split(
                jax.random.fold_in(key, 1), N_KEYS))
        self.rounds = 0
        self.phase_s["inputs"] = time.perf_counter() - t
        t = time.perf_counter()
        with jax.profiler.TraceAnnotation("bench.compile"):
            losses = self.step()
        self.first_losses = np.asarray(losses, np.float64)
        self.phase_s["first_round"] = time.perf_counter() - t
        self.p1 = host_params(self.st)
        t = time.perf_counter()
        self.step()                                    # warm, second shape
        self.phase_s["second_round"] = time.perf_counter() - t
        return self

    def args(self):
        """The next round's arguments: its key, the state and the data."""
        return self.keys[self.rounds % N_KEYS], self.st, self.data

    def step(self):
        with jax.profiler.TraceAnnotation("bench.round.dispatch"):
            self.st, losses = self.fn(*self.args())
        with jax.profiler.TraceAnnotation("bench.round.wait"):
            jax.block_until_ready((self.st, losses))
        self.rounds += 1
        return losses

    def window(self, seconds):
        """Rounds back to back for ``seconds``: (per-round seconds, window
        seconds, losses of each round)."""
        times, losses = [], []
        cache0 = self.fn._cache_size()
        t0 = time.perf_counter()
        with jax.profiler.TraceAnnotation("bench.window"):
            while True:
                ts = time.perf_counter()
                losses.append(self.step())
                te = time.perf_counter()
                times.append(te - ts)
                if te - t0 >= seconds:
                    break
        self.compiled_in_window = self.fn._cache_size() - cache0
        return times, te - t0, losses

    def free(self):
        self.st = self.data = self.fn = None
        gc.collect()


def reference_round(cell, seed, mode="f32"):
    """The plain reference's first round from ``seed`` on the default
    device, in float32 or in a control's ``mode``: (losses, params before,
    params after, step-0 gradient norm of each leaf), all on the host."""
    cfg = cell["config"]
    ref = S.reference(cfg)
    st, data = S.family(cfg).make_inputs(gen.seed_key(seed), cfg)
    params = {"cp": st["cp"], "sp": st["sp"]}
    del st
    out = ref.sfpl_round(
        params, data, model=gen.items(cfg["model"]),
        fleet=gen.items(cfg["fleet"]), opt=gen.items(cfg["optimizer"]),
        mode=mode)
    losses, after, g0 = jax.device_get(out)
    p0 = jax.device_get(params)
    del params, data, out
    return (np.asarray(losses, np.float64), p0, after,
            np.asarray(g0, np.float64))


def leaf_changes(p0, p1, after):
    """(paths, and for each leaf the norms of the program's and of the
    reference's change over the round)."""
    leaves = jax.tree_util.tree_leaves_with_path(p0)
    f64 = lambda a: np.asarray(a, np.float64)
    change = lambda t: [float(np.linalg.norm(f64(b) - f64(a)))
                        for (_, a), b in zip(leaves,
                                             jax.tree_util.tree_leaves(t))]
    return [jax.tree_util.keystr(p) for p, _ in leaves], change(p1), \
        change(after)


def numbers(loss_prog, loss_ref, paths, d_prog, d_ref, g0):
    """The numbers of one run that its cell's limits may compare:

    * ``loss0``, ``loss1`` — the absolute gaps of the round's step-0 and
      step-1 pooled losses to the reference's;
    * ``dparam`` — the worst leaf's gap between the program's and the
      reference's norm of the parameter change over the round, over the
      larger of that leaf's and the median leaf's reference norm;
    * ``dparam_med`` — the median leaf's such gap;
    * ``dparam_client`` — the worst such gap among the client's leaves,
      which the routed-back gradients and the FedAvg write.

    Leaves whose step-0 reference gradient is under ``ZERO_GRAD`` of the
    median leaf's are left out of the last three."""
    keep = [i for i in range(len(paths))
            if g0[i] >= ZERO_GRAD * float(np.median(g0))]
    med = statistics.median(d_ref[i] for i in keep)
    gaps = {paths[i]: abs(d_prog[i] - d_ref[i]) / max(d_ref[i], med)
            for i in keep}
    worst = sorted(gaps, key=gaps.get, reverse=True)
    loss_prog = np.asarray(loss_prog, np.float64)
    nums = {"loss0": abs(loss_prog[0] - loss_ref[0]),
            "loss1": (abs(loss_prog[1] - loss_ref[1]) if len(loss_ref) > 1
                      else math.nan),
            "dparam": gaps[worst[0]],
            "dparam_med": statistics.median(gaps.values()),
            "dparam_client": max(v for k, v in gaps.items()
                                 if k.startswith("['cp']"))}
    detail = {"ref_losses": np.asarray(loss_ref).tolist(),
              "loss_gaps": np.abs(loss_prog - loss_ref).tolist(),
              "worst_leaves": [[k, gaps[k]] for k in worst[:3]],
              "left_out": [paths[i] for i in range(len(paths))
                           if i not in keep]}
    return {k: float(v) for k, v in nums.items()}, detail


def compare(prog_losses, p1, ref):
    """``numbers`` of a program's first round against the reference's."""
    losses, p0, after, g0 = ref
    return numbers(prog_losses, losses, *leaf_changes(p0, p1, after), g0)


def judge(nums, limits):
    """(correct, {number: value and limit}) over the numbers that the
    cell's limits name."""
    checks = {k: {"value": float(nums[k]), "limit": limits[k]}
              for k in limits}
    if not checks:
        return False, {k: {"value": float(v), "limit": None}
                       for k, v in nums.items()}
    ok = all(c["limit"] is not None and math.isfinite(c["value"])
             and c["value"] <= c["limit"] for c in checks.values())
    return ok, checks


def chip_peak_bytes(stats):
    """A chip's peak of device memory: the peak of the buffers in use
    plus the peak that the runtime reserved for the programs' temporaries
    (which ``peak_bytes_in_use`` leaves out on a TPU)."""
    return (stats.get("peak_bytes_in_use", 0)
            + stats.get("peak_bytes_reserved", 0))


def p90(xs):
    if len(xs) < 2:
        return xs[0]
    return statistics.quantiles(xs, n=10, method="inclusive")[-1]


class Ctx:
    """What a per-layer metric reader gets; ``scopes`` is the window
    executable's scope map (``scopes.of_program``), or ``None``."""

    def __init__(self, cell, tr, lo, hi, rounds, peaks, scopes=None):
        self.config, self.traffic = cell["config"], cell["traffic"]
        self.chips = cell["chips"]
        self.family = S.family(self.config)
        self.peaks, self.flops, self.tr = peaks, F, tr
        self.scopes = scopes
        self.lo, self.hi = lo, hi
        self.window_ns = hi - lo
        self.window_s = self.window_ns / 1e9
        self.rounds_traced = rounds
        fl = self.config["fleet"]
        self.samples_traced = rounds * F.pool_rows(fl) * fl[
            "steps_per_round"]

    def busy_ns(self):
        return TR.busy_ns(self.tr, self.lo, self.hi)

    def op_time_ns(self, match):
        return TR.op_time_ns(self.tr, self.lo, self.hi, match)


def configure(cell):
    """The compile cache in the checkout (every program cached, however
    fast it compiled) and the traffic's matmul precision."""
    from repro.launch.compile_cache import enable_compile_cache
    enable_compile_cache()
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    jax.config.update("jax_default_matmul_precision",
                      cell["traffic"]["matmul_precision"])


def run_cell(cell, seed, seconds, trace=False, peaks=None,
             t_start=T_START, configure_jax=True):
    """One run; returns the result dict that ``main`` prints.
    ``configure_jax=False`` leaves JAX's global settings alone (tests)."""
    if configure_jax:
        configure(cell)
    devs = jax.devices()[:cell["chips"]]
    if trace and peaks is None:
        peaks = F.peaks(devs[0].device_kind)
    fl = cell["config"]["fleet"]
    per_round = F.pool_rows(fl) * fl["steps_per_round"]

    prog = Program(cell).start(seed)
    setup_s = time.perf_counter() - t_start
    if trace:
        shutil.rmtree(TRACE_DIR, ignore_errors=True)
        jax.profiler.start_trace(str(TRACE_DIR))
    times, window_s, losses = prog.window(seconds)
    if trace:
        jax.profiler.stop_trace()
    stats = [d.memory_stats() or {} for d in devs]
    peak = max(chip_peak_bytes(s) for s in stats)
    log(f"memory_stats of the first chip: {stats[0]}")
    all_losses = np.asarray(jax.device_get(losses), np.float64)
    failed = int((~np.isfinite(all_losses)).any(axis=1).sum())
    if prog.compiled_in_window:
        log(f"warning: {prog.compiled_in_window} compile(s) inside the "
            f"window")
    scopes = SC.of_program(prog) if trace else None
    first, p1 = prog.first_losses, prog.p1
    prog.free()

    res = {"correct": False, "attempted": len(times), "failed": failed,
           "metrics": {}, "device": {
               "platform": devs[0].platform, "kind": devs[0].device_kind,
               "count": len(devs), "memory_peak_bytes": int(peak)}}
    rounds = len(times)
    values = {"samples_per_s": (rounds * per_round / window_s,
                                "samples/s"),
              "peak_hbm_gib": (peak / 2 ** 30, "GiB"),
              "setup_s": (setup_s, "s"),
              "round_s.p90": (p90(times), "s")}
    if not trace:
        for m in cell["end_to_end"]:
            if m["name"] in values:
                v, unit = values[m["name"]]
                res["metrics"][m["name"]] = {"value": v, "unit": unit}
    else:
        tr = TR.load(str(TRACE_DIR))
        shutil.rmtree(TRACE_DIR, ignore_errors=True)
        lo, hi = TR.span(tr, "bench.window")
        ctx = Ctx(cell, tr, lo, hi, rounds, peaks, scopes)
        if scopes is not None:
            SC.log_breakdown(ctx)
        for m in cell["per_layer"]:
            v = S.reader(m["name"]).read(ctx)
            if v is not None:
                res["metrics"][m["name"]] = {"value": v, "unit": m["unit"]}
        busy = ctx.busy_ns()
        res["device"]["busy_s"] = (sum(busy.values()) / len(busy) / 1e9
                                   if busy else 0.0)
        res["device"]["window_s"] = ctx.window_s
        res["breakdown"] = {"device_ops": TR.top_ops(tr, lo, hi),
                            "idle_gaps": TR.idle_gaps(tr, lo, hi)}
    log(f"set-up phases (s): {prog.phase_s}")
    log(f"rounds {rounds}, window {window_s!r} s, set-up {setup_s!r} s, "
        f"round s median {statistics.median(times)!r}, "
        f"peak bytes {peak}")

    nums, detail = compare(first, p1, reference_round(cell, seed))
    ok, checks = judge(nums, cell["limits"])
    res["correct"] = ok
    log(f"numbers: {json.dumps(nums)}")
    log(f"reference: {json.dumps(detail)}")
    for k, c in checks.items():
        log(f"check {k} = {c['value']!r} (limit {c['limit']!r})")
    res["checks"] = checks
    return res


def main(argv=None):
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)
    cell = S.cell(args.workload)
    require_chips(cell["chips"])
    with contextlib.redirect_stdout(sys.stderr):
        res = run_cell(cell, args.seed, args.seconds, trace=bool(args.trace))
    print(json.dumps(res))


if __name__ == "__main__":
    main()
