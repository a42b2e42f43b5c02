"""The host plane of a profiler trace, read further than ``devtrace``
reads it: the program's ``generation`` step markers, every program span
(``repro.obs.telemetry.PHASES``, nested ones included) on the thread
that runs the generations, and the runtime's host-to-device copies on
its own threads.  ``idle_by_cause`` splits the device's idle time in
``devtrace``'s window by what kept the device waiting.

Like ``devtrace.reduce_events``, ``idle_by_cause`` works on plain
tuples, so it is tested without a chip.  A trace of a program that has
no such spans or markers reads as nothing found, never as an error.
"""
from __future__ import annotations

from typing import Dict, List, Sequence, Tuple

from bench.devtrace import clip, union

Interval = Tuple[str, float, float]          # (name, start_ns, end_ns)

# the program's span names (repro.obs.telemetry.PHASES)
SPANS = ("init", "sample", "availability", "accounting", "fill_train",
         "eval", "stage", "download", "launch", "compile", "cache_load",
         "objectives", "aggregate", "codec_encode", "codec_decode",
         "host_fetch")
STEP = "generation"
# The TPU runtime's host-to-device copy work, by event-name prefix, as a
# capture of cifar12.xdevice500 on a TPU v5 lite names it: on the
# ``pjrt-tpu-tasks`` threads ``XlaLinearize`` lays a staged array out in
# the device's tiling (its ``Linearize`` / ``Transpose*`` children nest
# inside it), then ``tpu::System::TransferToDevice`` (and its
# ``=>IssueEvent`` / ``=>IssueEvent=>Done`` events, the last on an
# ``EventFDAsyncWorker`` thread) hands it to the transfer engine.
COPY_EVENTS = ("XlaLinearize", "tpu::System::TransferToDevice")
STAGING = "staging"
BETWEEN = "between spans"


def load_host(path: str) -> Dict[str, List]:
    """``steps``: (step_num, start_ns, end_ns) of every ``generation``
    marker; ``spans``: the program spans on the markers' thread;
    ``copies``: copy events on every other host thread."""
    from jax.profiler import ProfileData
    out: Dict[str, List] = {"steps": [], "spans": [], "copies": []}
    pd = ProfileData.from_file(path)
    for plane in pd.planes:
        if not plane.name.startswith("/host:"):
            continue
        for line in plane.lines:
            events = list(line.events)
            steps = [(dict(e.stats).get("step_num"), e.start_ns,
                      e.start_ns + e.duration_ns)
                     for e in events if e.name == STEP]
            if steps:
                out["steps"].extend(steps)
                out["spans"].extend(
                    (e.name, e.start_ns, e.start_ns + e.duration_ns)
                    for e in events if e.name in SPANS)
            else:
                out["copies"].extend(
                    (e.name, e.start_ns, e.start_ns + e.duration_ns)
                    for e in events if e.name.startswith(COPY_EVENTS))
    return out


def window(spans: Sequence[Interval]):
    """``devtrace``'s window: the first ``sample`` to the last
    ``aggregate`` (None when either is missing)."""
    starts = [s for n, s, _ in spans if n == "sample"]
    ends = [e for n, _, e in spans if n == "aggregate"]
    if not starts or not ends:
        return None
    return min(starts), max(ends)


def span_path(spans: Sequence[Interval], t: float) -> str:
    """The nesting path of the innermost span around ``t``
    (``fill_train/stage/download``), or ``between spans``."""
    around = sorted(((s, -e, n) for n, s, e in spans if s <= t <= e))
    return "/".join(n for _, _, n in around) or BETWEEN


def _subtract(iv: Tuple[float, float], cover) -> List[Tuple[float, float]]:
    """The parts of ``iv`` outside the sorted disjoint ``cover``."""
    out, s = [], iv[0]
    for cs, ce in cover:
        if ce <= s or cs >= iv[1]:
            continue
        if cs > s:
            out.append((s, cs))
        s = max(s, ce)
    if s < iv[1]:
        out.append((s, iv[1]))
    return out


def idle_by_cause(devices: Sequence[Dict], spans: Sequence[Interval],
                  copies: Sequence[Interval]) -> Dict[str, float]:
    """Seconds of device idle time in the window by cause, averaged over
    the devices: idle while a host-to-device copy runs goes to
    ``staging``, the rest to the span path the host was in, or
    ``between spans``.  The causes sum to ``window_s - busy_s`` of
    ``devtrace.reduce_events``.  ``staging`` is there only when the
    trace holds copy events.  Empty when there is no window or no
    device."""
    win = window(spans)
    if win is None or not devices:
        return {}
    w0, w1 = win
    inflight = union([(s, e) for _, s, e in copies])
    edges = sorted({x for _, s, e in spans for x in (s, e)})
    out: Dict[str, float] = {STAGING: 0.0} if copies else {}
    for dev in devices:
        busy = union(clip([(s, e) for _, s, e in dev["modules"]], w0, w1))
        for idle in _subtract((w0, w1), busy):
            for cs, ce in clip(inflight, *idle):
                out[STAGING] = out.get(STAGING, 0.0) + (ce - cs) / 1e9
            for s, e in _subtract(idle, inflight):
                cuts = [s] + [x for x in edges if s < x < e] + [e]
                for a, b in zip(cuts, cuts[1:]):
                    cause = span_path(spans, (a + b) / 2)
                    out[cause] = out.get(cause, 0.0) + (b - a) / 1e9
    return {k: v / len(devices) for k, v in out.items()}
