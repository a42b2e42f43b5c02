"""Readings that the limits of ``correct`` are set from.

    python3 bench/calibrate.py --workload <cell> --seeds 1,2,3 \
        [--control-seeds 1,2,3] [--out readings.json]

For each seed, in one process (so the programs compile once): the
program's first master update and first two evaluations, as a run's
set-up makes them, compared with the plain reference: the sound
readings.  For each control seed, three things put in the program's
place and compared the same way: the control (``reference.control``:
the reference one precision step below the configuration's, its
training chain and its scoring of the program's masters), the reference
with half of every batch left out and the mean taken over the rest, and
the program's answers with the first and last keys' error rates
swapped.  A state left unchanged reads 1 by the update gaps' measure
and needs no run.  Needs the chip the cell asks for.
"""
from __future__ import annotations

import argparse
import json
import os
import sys
import time

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path[:1] = [ROOT, os.path.join(ROOT, "src")]


def prefix_run(cell, seed):
    """The program's recorded calls and captured masters up to the end
    of set-up, and the shards those calls touched."""
    from bench import harness
    clients = harness.build_fleet(seed, cell.traffic, cell.config["search"],
                                  cell.config["model"]["image"])
    eng, rec, _ = harness.build_engine(cell, seed, clients, trace=False)

    def stop(gen, _report):
        if gen == harness.WARM_GENS:
            raise harness.WindowClosed

    try:
        eng.run(callback=stop)
    except harness.WindowClosed:
        pass
    steps = harness.reference_steps(rec.calls)
    masters = list(rec.masters)
    del eng, rec
    shards = {}
    for st in steps:
        ids = ([c for g in st["groups"] for c in g] if st["kind"] == "fill"
               else st["clients"])
        for cid in ids:
            c = clients[int(cid)]
            shards[int(cid)] = (c.train[0], c.train[1], c.test[0], c.test[1])
    return steps, masters, shards


def swapped_answers(errs):
    """The planted answer fault: the first and last keys' error rates of
    every evaluation exchanged."""
    out = []
    for e in errs:
        e = e.copy()
        e[0], e[-1] = e[-1], e[0]
        out.append(e)
    return out


def readings(cell, seed: int, control: bool, log=print):
    from bench import correctness, harness, reference
    from bench.reference import Reference
    t = time.perf_counter()
    steps, masters, shards = prefix_run(cell, seed)
    prog_errs = [st["errs"] for st in steps if st["kind"] == "eval"]
    t_prog = time.perf_counter() - t
    t = time.perf_counter()
    ref = Reference(cell.config)
    first = harness.first_update(steps)
    ref_m = ref.run(seed, first, shards)
    ref_e = ref.errors(steps, shards, masters)
    t_ref = time.perf_counter() - t
    out = {"seed": seed, "program_s": t_prog, "reference_s": t_ref,
           "sound": correctness.compare(masters, prog_errs, ref_m, ref_e),
           "errs": [e.tolist() for e in prog_errs],
           "ref_errs": [e.tolist() for e in ref_e]}
    out["sound_detail"] = correctness.worst_leaves(masters, ref_m)
    if control:
        ctl = reference.control(cell.config)
        out["control"] = correctness.compare(
            ctl.run(seed, first, shards), ctl.errors(steps, shards, masters),
            ref_m, ref_e)
        # a training fault: the fitness program scores the master it is
        # given, so its error rates read as the sound ones
        half = Reference(cell.config, half_batch=True).run(seed, first,
                                                           shards)
        out["half_batch"] = correctness.compare(half, ref_e, ref_m, ref_e)
        out["swapped_answers"] = correctness.compare(
            masters, swapped_answers(prog_errs), ref_m, ref_e)
    log(json.dumps(out), flush=True)
    return out


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", default="")
    ap.add_argument("--control-seeds", default="")
    ap.add_argument("--out")
    args = ap.parse_args(argv)
    from bench import harness
    cell = harness.load_cell(args.workload)
    import jax
    harness.enable_cache(jax)
    harness.set_precision(jax, cell)
    harness.check_chips(jax, cell.chips)
    controls = {int(s) for s in args.control_seeds.split(",") if s}
    seeds = [int(s) for s in args.seeds.split(",") if s]
    seeds += sorted(controls - set(seeds))
    res = [readings(cell, s, s in controls) for s in seeds]
    if args.out:
        os.makedirs(os.path.dirname(os.path.abspath(args.out)),
                    exist_ok=True)
        with open(args.out, "w") as f:
            json.dump(res, f, indent=1)
    return 0


if __name__ == "__main__":
    sys.exit(main())
