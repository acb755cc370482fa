"""Readings that the limits of a cell's correctness check are set from,
on the chip, in one process:

    python3 benchmarks/chip/calibrate.py --workload <cell> \
        --seeds 11,12,... --control-seeds 21,22,23 \
        --faults half_batch,misroute --fault-seeds 31,32,33

* sound: the program's first round against the reference, per seed (the
  lower readings);
* control: the reference computed in the precision below the one the
  traffic states (``control`` in ``traffic/<traffic>.json``), in the
  program's place;
* faults: the program with each fault of ``faults.py`` planted;
* look (``--look``, ``--look-seeds``): the reference computed in another
  arithmetic in the program's place, to see how far rounding alone moves
  the numbers; ``--look1-seeds`` the same over a round of one local step,
  to see how far the later steps grow it; ``--kernels-off-seeds`` the
  program built with its fused Pallas kernels off (the launcher's
  ``use_kernel=False``).

Prints one JSON object of every reading; the benchmark's own runs never
run this.
"""
from __future__ import annotations

import argparse
import contextlib
import json
import sys
import time
from pathlib import Path

import numpy as np

sys.path.insert(0, str(Path(__file__).resolve().parents[1]))
from chip import run as R  # noqa: E402  (sets up the import paths)
from chip import faults  # noqa: E402
from chip import spec as S  # noqa: E402

import jax  # noqa: E402

KEYS = ("loss0", "loss1", "dparam", "dparam_med", "dparam_client")


def program_rounds(cell, seeds, fault=None, **build):
    """{seed: (first-round losses, params after it)} of the program, with
    ``fault`` planted."""
    out = {}
    if not seeds:
        return out
    with faults.planted(fault) if fault else contextlib.nullcontext():
        prog = R.Program(cell, **build)
        for s in seeds:
            prog.start(s)
            out[s] = (prog.first_losses, prog.p1)
            prog.st = prog.data = None
        prog.free()
    return out


def main():
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True)
    ap.add_argument("--control-seeds", default="")
    ap.add_argument("--faults", default="")
    ap.add_argument("--fault-seeds", default="")
    ap.add_argument("--look", default="bf16",
                    help="arithmetic of the reference read beside it")
    ap.add_argument("--look-seeds", default="")
    ap.add_argument("--look1-seeds", default="")
    ap.add_argument("--kernels-off-seeds", default="")
    args = ap.parse_args()
    ints = lambda s: [int(x) for x in s.split(",") if x]
    cell = S.cell(args.workload)
    R.require_chips(cell["chips"])
    R.configure(cell)
    t0 = time.perf_counter()
    refs = {}

    def ref(seed):
        if seed not in refs:
            refs[seed] = R.reference_round(cell, seed)
        return refs[seed]

    out = {"workload": args.workload, "sound": {}, "control": {},
           "faults": {}, "raw": {}}

    def raw(name, losses, p1, r):
        """Everything any number is made from, for a later look: every
        leaf's change norms and the norm of the program's change less the
        reference's, and each client's own."""
        paths, d_prog, d_ref = R.leaf_changes(r[1], p1, r[2])
        f64 = lambda a: np.asarray(a, np.float64)
        leaves = lambda t: jax.tree_util.tree_leaves(t)
        d_diff = [float(np.linalg.norm(f64(a) - f64(b)))
                  for a, b in zip(leaves(p1), leaves(r[2]))]
        clients = {}
        for (path, a0), a1, ar in zip(
                jax.tree_util.tree_leaves_with_path(r[1]["cp"]),
                leaves(p1["cp"]), leaves(r[2]["cp"])):
            a0, a1, ar = f64(a0), f64(a1), f64(ar)
            ax = tuple(range(1, a0.ndim))
            nrm = lambda t: np.sqrt(np.sum(np.square(t), axis=ax)).tolist()
            clients[jax.tree_util.keystr(path)] = {
                "prog": nrm(a1 - a0), "ref": nrm(ar - a0),
                "diff": nrm(a1 - ar)}
        out["raw"][name] = {"prog_losses": list(map(float, losses)),
                            "ref_losses": r[0].tolist(), "paths": paths,
                            "d_prog": d_prog, "d_ref": d_ref,
                            "d_diff": d_diff, "g0": r[3].tolist(),
                            "clients": clients}
    out["detail"] = {}

    def record(group, s, losses, p1, r):
        raw(f"{group} {s}", losses, p1, r)
        nums, d = R.compare(losses, p1, r)
        out.setdefault(group, {})[s] = nums
        out["detail"][f"{group} {s}"] = d
        R.log(f"{group} seed {s}: {nums} {d}")

    for s, (losses, p1) in program_rounds(cell, ints(args.seeds)).items():
        record("sound", s, losses, p1, ref(s))
    for s, (losses, p1) in program_rounds(
            cell, ints(args.kernels_off_seeds), use_kernel=False).items():
        record("kernels_off", s, losses, p1, ref(s))
    for s in ints(args.look_seeds):
        l_losses, _, l_after, _ = R.reference_round(cell, s, mode=args.look)
        record("look", s, l_losses, l_after, ref(s))
    one = json.loads(json.dumps(cell))
    one["config"]["fleet"]["steps_per_round"] = 1
    for s in ints(args.look1_seeds):
        l_losses, _, l_after, _ = R.reference_round(one, s, mode=args.look)
        record("look1", s, l_losses, l_after, R.reference_round(one, s))
    mode = cell["traffic"]["control"]
    for s in ints(args.control_seeds):
        c_losses, _, c_after, _ = R.reference_round(cell, s, mode=mode)
        record("control", s, c_losses, c_after, ref(s))
    for f in [x for x in args.faults.split(",") if x]:
        for s, (losses, p1) in program_rounds(cell, ints(args.fault_seeds),
                                              fault=f).items():
            raw(f"{f} {s}", losses, p1, ref(s))
            out["faults"].setdefault(f, {})[s], d = R.compare(
                losses, p1, ref(s))
            out["detail"][f"{f} {s}"] = d
            R.log(f"fault {f} seed {s}: {out['faults'][f][s]}")
    for group in ("sound", "kernels_off", "look", "look1"):
        if out.get(group):
            out[f"{group}_max"] = {k: max(v[k] for v in out[group].values())
                                   for k in KEYS}
    if out["control"]:
        out["control_min"] = {k: min(v[k] for v in out["control"].values())
                              for k in KEYS}
    out["faults_min"] = {f: {k: min(v[k] for v in r.values())
                             for k in KEYS}
                         for f, r in out["faults"].items()}
    out["seconds"] = time.perf_counter() - t0
    print(json.dumps(out))


if __name__ == "__main__":
    sys.exit(main())
