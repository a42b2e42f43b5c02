"""Reduction of a profiler trace to the device numbers the per-layer
metrics read.

``load`` reads an ``.xplane.pb`` with ``jax.profiler.ProfileData``: the
program executions ("XLA Modules" line) and the operations ("XLA Ops"
line) of every TPU device plane, and the program's own telemetry spans
(``jax.profiler.TraceAnnotation``s named after the ``repro.obs`` phases)
on the host plane.  Host and device events share one clock there
(nanoseconds from the start of the capture).  ``reduce_events`` does the
arithmetic on plain tuples, so it is tested without a chip.

The traced window runs from the start of the first ``sample`` span (a
generation's first host step) to the end of the last ``aggregate`` span
(its NSGA-II selection, the last).  Busy time is the union of program
execution intervals inside it, averaged over the devices.
"""
from __future__ import annotations

import re
from typing import Dict, List, Sequence, Tuple

Interval = Tuple[str, float, float]          # (name, start_ns, end_ns)

# the program's span names (repro.obs.telemetry.PHASES)
SPANS = ("sample", "availability", "download", "fill_train", "aggregate",
         "eval", "codec_encode", "codec_decode", "host_fetch")
EVAL_PROGRAM = "fused_eval"
ALG3_PROGRAM = "fill_aggregate"
CODEC_PROGRAM = "roundtrip"
TOP = 10


def load(path: str):
    """(devices, spans): per TPU plane a dict of ``modules`` and ``ops``
    intervals, and the host's telemetry spans."""
    from jax.profiler import ProfileData
    pd = ProfileData.from_file(path)
    devices, spans = [], []
    for plane in pd.planes:
        if plane.name.startswith("/device:TPU:"):
            dev = {"modules": [], "ops": []}
            for line in plane.lines:
                key = {"XLA Modules": "modules",
                       "XLA Ops": "ops"}.get(line.name)
                if key:
                    dev[key] = [(e.name, e.start_ns,
                                 e.start_ns + e.duration_ns)
                                for e in line.events]
            devices.append(dev)
        elif plane.name.startswith("/host:"):
            for line in plane.lines:
                spans.extend((e.name, e.start_ns, e.start_ns + e.duration_ns)
                             for e in line.events if e.name in SPANS)
    return devices, spans


def program_name(module: str) -> str:
    """``jit_fused_fill(1336...)`` -> ``fused_fill``."""
    name = re.sub(r"\(\d+\)$", "", module)
    return name[4:] if name.startswith("jit_") else name


def op_name(op: str) -> str:
    """An HLO instruction as the trace names it, cut to its name and
    result type: ``%fusion.59 = bf16[10,50,32,32,64]``."""
    head = op.split("{", 1)[0]
    return head[:80].strip()


def union(intervals: Sequence[Tuple[float, float]]) -> List[Tuple[float,
                                                                  float]]:
    out: List[List[float]] = []
    for s, e in sorted(intervals):
        if out and s <= out[-1][1]:
            out[-1][1] = max(out[-1][1], e)
        else:
            out.append([s, e])
    return [(s, e) for s, e in out]


def clip(intervals, w0, w1):
    return [(max(s, w0), min(e, w1)) for s, e in intervals
            if e > w0 and s < w1]


def leaves(ops: Sequence[Interval]) -> List[Interval]:
    """The operations that contain no other (the trace nests a loop's
    body operations inside the loop's own event)."""
    srt = sorted(ops, key=lambda o: (o[1], -o[2]))
    out = []
    for i, o in enumerate(srt):
        if i + 1 < len(srt) and srt[i + 1][1] < o[2]:
            continue
        out.append(o)
    return out


def innermost_span(spans: Sequence[Interval], t: float) -> str:
    best = None
    for name, s, e in spans:
        if s <= t <= e and (best is None or e - s < best[1]):
            best = (name, e - s)
    return best[0] if best else "between spans"


def reduce_events(devices: Sequence[Dict], spans: Sequence[Interval]
                  ) -> Dict:
    """Busy and window seconds, device seconds by role, the top
    operations and the longest idle gaps (see the module docstring)."""
    starts = [s for n, s, _ in spans if n == "sample"]
    ends = [e for n, _, e in spans if n == "aggregate"]
    if not devices or not starts or not ends:
        return None
    w0, w1 = min(starts), max(ends)
    busy, roles = [], []
    for dev in devices:
        mods = [(program_name(n), s, e) for n, s, e in dev["modules"]
                if e > w0 and s < w1]
        iv = union(clip([(s, e) for _, s, e in mods], w0, w1))
        busy.append(sum(e - s for s, e in iv))
        role = {"eval_s": 0.0, "fill_s": 0.0, "alg3_s": 0.0, "codec_s": 0.0}
        for name, s, e in mods:
            d = (min(e, w1) - max(s, w0)) / 1e9
            role["eval_s" if EVAL_PROGRAM in name else "fill_s"] += d
            if ALG3_PROGRAM in name:
                role["alg3_s"] += d
            if CODEC_PROGRAM in name:
                role["codec_s"] += d
        roles.append(role)
    n = len(devices)
    out = {k: sum(r[k] for r in roles) / n for k in roles[0]}
    out["busy_s"] = sum(busy) / n / 1e9
    out["window_s"] = (w1 - w0) / 1e9

    # the device operations that took most time, by program and name
    first = devices[0]
    mods0 = sorted((s, e, program_name(nm)) for nm, s, e in first["modules"])
    tot: Dict[str, float] = {}
    j = 0
    for name, s, e in sorted(leaves([o for o in first["ops"]
                                     if o[2] > w0 and o[1] < w1]),
                             key=lambda o: o[1]):
        while j + 1 < len(mods0) and mods0[j + 1][0] <= s:
            j += 1
        prog = mods0[j][2] if mods0 and mods0[j][0] <= s else "?"
        key = f"{prog}/{op_name(name)}"
        tot[key] = tot.get(key, 0.0) + (min(e, w1) - max(s, w0)) / 1e9
    out["top_ops"] = [[k, v] for k, v in
                      sorted(tot.items(), key=lambda kv: -kv[1])[:TOP]]

    # the longest idle gaps on the first device, by what the host was in
    iv = union(clip([(s, e) for _, s, e in first["modules"]], w0, w1))
    edges = [w0] + [x for s, e in iv for x in (s, e)] + [w1]
    gaps = [(edges[i], edges[i + 1]) for i in range(0, len(edges), 2)
            if edges[i + 1] > edges[i]]
    gaps.sort(key=lambda g: g[0] - g[1])
    out["idle_gaps"] = [[innermost_span(spans, (s + e) / 2), (e - s) / 1e9]
                        for s, e in gaps[:TOP]]
    return out


def reduce(path: str) -> Dict:
    return reduce_events(*load(path))
