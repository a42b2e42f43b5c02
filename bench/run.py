"""Run one benchmark cell once and print its result line.

    python3 bench/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

The cell is an entry of ``workloads`` in ``BENCHMARK.json``.  With
``--trace 0`` the result carries the cell's end-to-end metrics; with
``--trace 1`` a profiled run reads its per-layer metrics.  Either way
the run ends by comparing what the timed search produced with the plain
reference (``bench/reference.py``); the numbers compared, each with its
limit, are the last lines on standard error and the ``checks`` key of
the result.  The last line of standard output is the result, one JSON
object.  The run needs a TPU with as many chips as the cell asks for,
and exits non-zero without a result otherwise.
"""
import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import sys  # noqa: E402

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
# import the benchmark as the ``bench`` package and the system under test
# from ``src``; this directory itself is not a place to import from
sys.path[:1] = [ROOT, os.path.join(ROOT, "src")]


def log(*args):
    print(*args, file=sys.stderr, flush=True)


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)

    if not os.path.isdir(os.path.join(ROOT, "src", "repro")):
        log(f"bench/run.py: no system under test at {ROOT}/src/repro")
        return 2
    from bench import harness
    cell = harness.load_cell(args.workload)
    import jax
    harness.enable_cache(jax)
    harness.set_precision(jax, cell)
    try:
        devices = harness.check_chips(jax, cell.chips)
    except harness.ChipError as e:
        log(f"bench/run.py: {e}")
        return 3
    out = harness.run_cell(cell, args.seed, args.seconds, bool(args.trace),
                           T_START, devices=devices, log=log)
    for name, c in out["checks"].items():
        verdict = "ok" if c["value"] <= c["limit"] else "FAILED"
        log(f"check {name} {c['value']!r} <= {c['limit']!r} {verdict}")
    print(json.dumps(out), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
