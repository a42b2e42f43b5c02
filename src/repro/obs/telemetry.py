"""`repro.obs` core: zero-overhead telemetry for the federated engine.

The paper's headline claim is *real-time* federated NAS, and every
direction the ROADMAP names next (async buffered aggregation, adaptive
``ServerPolicy``s, codec auto-tuning) feeds off recorded round signals —
where a round's time goes, whether a config silently retraces the fused
programs, how device/host memory behaves across a sweep.  This module
turns those questions into engine truth:

  * ``Telemetry`` — nestable **phase spans** (``PHASES``) recorded as
    monotonic ``time.perf_counter`` durations and accumulated per round
    under their nesting path (e.g. ``"fill_train/stage/download"``).
    Every span is also a ``jax.profiler.TraceAnnotation`` and every
    generation a ``jax.profiler.StepTraceAnnotation("generation")``, so
    a profiler capture (``TelemetryConfig.profile_gens``) shows the
    same structure on the clock of the device planes.  Compilation and
    compilation-cache loads, reported by ``jax.monitoring``, are booked
    as ``compile`` spans under the span open at the time.
  * ``RoundEvent`` — one structured record per federated round: span
    durations and call counts, **recompile deltas** (trace-count per
    jitted program, see ``traced``), **resource gauges** (live device
    bytes, host RSS, lazy-fleet materialization, stacked-store LRU
    hit/miss), **counters** (``staged_bytes``) and the round's
    **CommStats deltas** — pushed to the configured sink and kept in an
    in-memory ring.
  * ``traced`` — wraps the *pre-jit* Python callable of every backend
    program so each ``jax.jit`` trace increments a per-program counter
    (tracing runs the Python body; dispatches do not) and the program
    body is labeled with ``jax.named_scope``.  This is what makes the
    "fused = 2·gens + 1 dispatches, compiled once" invariant directly
    observable instead of trusted.
  * ``NULL_TELEMETRY`` — the disabled path.  ``FedEngine`` only
    constructs a real ``Telemetry`` (and the ``InstrumentedBackend``
    wrapper) when ``RunConfig.telemetry`` is enabled; everything else
    sees this shared no-op object whose spans are empty context
    managers.  Telemetry is therefore *bit-exactly* invisible when off
    — no numeric path changes, no extra dispatches — which
    ``tests/test_obs.py`` pins across every backend × fused pair.

Nothing here imports ``repro.engine`` — the engine depends on ``obs``,
never the reverse — so the gauges read engine state duck-typed
(``clients.materialized``, ``backend.cache_stats``, ...).
"""
from __future__ import annotations

import contextlib
import dataclasses
import functools
import os
import time
from typing import Any, Dict, List, Optional, Tuple

import jax
from jax import monitoring

from repro.obs.gauges import host_rss_bytes, live_device_bytes
from repro.obs.sinks import MemorySink, make_sink, parse_sink_spec

# The span vocabulary (nesting paths join these with "/"):
#   init         the strategy's run set-up (booked into generation 1)
#   sample       participant / client-group / offspring sampling
#   availability the ClientSimulator round draw
#   accounting   the round's traffic accounting on CommStats
#   fill_train   a backend training call (fill-train / FedAvg): the call,
#                not the device work it enqueues
#   eval         a backend evaluation call (likewise)
#   stage        assembling a jitted program's arguments (device gathers,
#                weights, keys, survivor masks)
#   download     host->device staging of stacked client shards
#   launch       the call into a jitted program, from call to return
#   compile      tracing, lowering, compiling or loading a program
#                (booked from jax.monitoring under the span open then)
#   cache_load   the compilation-cache read inside a compile
#   objectives   the FLOPs column of the objectives
#   aggregate    server-side NSGA-II selection bookkeeping
#   codec_encode uplink codec compression of the aggregated update
#   codec_decode downlink codec roundtrip of a broadcast payload
#   host_fetch   the per-generation device_get of fused eval counts
PHASES = ("init", "sample", "availability", "accounting", "fill_train",
          "eval", "stage", "download", "launch", "compile", "cache_load",
          "objectives", "aggregate", "codec_encode", "codec_decode",
          "host_fetch")

# jax.monitoring events booked as ``compile`` spans.  They nest (a jitted
# helper traces inside the program that calls it, the cache read sits
# inside the backend compile), so only their union is booked.
COMPILE_EVENTS = ("/jax/core/compile/jaxpr_trace_duration",
                  "/jax/core/compile/jaxpr_to_mlir_module_duration",
                  "/jax/core/compile/backend_compile_duration")
CACHE_LOAD_EVENT = "/jax/compilation_cache/cache_retrieval_time_sec"

# CommStats fields whose per-round deltas every RoundEvent carries
COMM_FIELDS = ("down_bytes", "up_bytes", "down_wire_bytes", "up_wire_bytes",
               "eval_down_bytes", "eval_up_bytes", "wasted_down_bytes",
               "wasted_down_wire_bytes", "client_train_passes")


@dataclasses.dataclass
class TelemetryConfig:
    """Every telemetry knob, validated at construction (like the rest of
    ``RunConfig``).  The default ``RunConfig.telemetry = None`` means
    *off* — constructing this object means *on* unless ``enabled=False``.

      * ``sink`` — where round events go beyond the in-memory ring:
        ``"memory"`` (ring only), ``"jsonl:<path>"`` (one JSON object
        per round, appended live) or ``"table"`` (a terminal table row
        per round).
      * ``ring`` — how many ``RoundEvent``s the in-memory ring retains
        (``EngineResult.telemetry.events``); older rounds fall off.
      * ``gauges`` — sample per-round resource gauges (live device
        bytes, host RSS, fleet/cache counters).  Off leaves the gauges
        dict empty but keeps spans/recompiles/comm.
      * ``profiler_dir`` / ``profile_gens`` — a profiler capture of
        generations ``first .. first + count - 1`` (``profile_gens =
        (first, count)``), written to ``profiler_dir``.  The capture
        starts and stops at generation boundaries with the Python
        tracer off, so it holds the chosen generations' spans, step
        markers and device planes and no compile of earlier ones.
        Both are set or neither."""
    enabled: bool = True
    sink: str = "memory"
    ring: int = 1024
    gauges: bool = True
    profiler_dir: Optional[str] = None
    profile_gens: Optional[Tuple[int, int]] = None

    def __post_init__(self):
        if self.ring < 1:
            raise ValueError(f"ring must be >= 1, got {self.ring}")
        parse_sink_spec(self.sink)   # unknown sink specs fail here
        if (self.profiler_dir is None) != (self.profile_gens is None):
            raise ValueError("profiler_dir and profile_gens=(first, count) "
                             "go together")
        if self.profile_gens is not None:
            first, count = self.profile_gens
            if first < 1 or count < 1:
                raise ValueError(f"profile_gens needs first >= 1 and "
                                 f"count >= 1, got {self.profile_gens}")
            self.profile_gens = (int(first), int(count))


@dataclasses.dataclass
class RoundEvent:
    """One federated round, as telemetry saw it.

    ``spans`` maps nesting paths (``"fill_train/stage/download"``) to
    summed seconds this round; ``span_counts`` the number of times each
    path was entered.  ``recompiles`` holds trace-count *deltas* — a
    jitted program that (re)compiled this round appears with the number
    of new traces, steady-state rounds carry an empty dict.  ``gauges``
    are point-in-time resource samples at round end; ``comm`` the
    round's ``CommStats`` field deltas; ``counters`` the round's summed
    program counters (``staged_bytes``: bytes of client shards handed
    to the device, train and test)."""
    gen: int
    round_s: float
    spans: Dict[str, float]
    span_counts: Dict[str, int]
    recompiles: Dict[str, int]
    gauges: Dict[str, Any]
    comm: Dict[str, float]
    counters: Dict[str, float] = dataclasses.field(default_factory=dict)


@dataclasses.dataclass
class TelemetryResult:
    """What ``EngineResult.telemetry`` carries after a telemetry-enabled
    run: the ring of ``RoundEvent``s plus the final per-program trace
    counts."""
    events: List[RoundEvent]
    trace_counts: Dict[str, int]

    def phase_totals(self) -> Dict[str, float]:
        """Total seconds per span path across all retained rounds."""
        out: Dict[str, float] = {}
        for e in self.events:
            for path, s in e.spans.items():
                out[path] = out.get(path, 0.0) + s
        return out


def traced(name: str, counts: Dict[str, int], fn):
    """Wrap a pre-``jax.jit`` Python callable so every trace increments
    ``counts[name]`` and the traced body sits under
    ``jax.named_scope(name)``.  Tracing runs the Python function;
    cached dispatches do not — so the counter is a faithful
    (re)compilation count per program, at zero dispatch cost.  The
    ``named_scope`` labels the program in profiler captures and HLO
    dumps; it never changes numerics."""

    @functools.wraps(fn)
    def wrapper(*args, **kwargs):
        counts[name] = counts.get(name, 0) + 1
        with jax.named_scope(name):
            return fn(*args, **kwargs)

    return wrapper


def innermost(backend):
    """The raw execution backend under any wrapper chain
    (``InstrumentedBackend`` -> ``CodecBackend`` -> backend)."""
    while hasattr(backend, "inner"):
        backend = backend.inner
    return backend


def attach(backend, telemetry) -> None:
    """Point every layer of a backend wrapper chain at ``telemetry``
    (each layer defaults to ``NULL_TELEMETRY`` as a class attribute)."""
    while backend is not None:
        backend.telemetry = telemetry
        backend = getattr(backend, "inner", None)


class _NullSpan:
    """A context manager that does nothing, shared by every
    ``NULL_TELEMETRY.span`` call."""
    __slots__ = ()

    def __enter__(self):
        return self

    def __exit__(self, *exc):
        return False


_NULL_SPAN = _NullSpan()


class NullTelemetry:
    """The disabled telemetry object: every hook is a no-op, every span
    an empty context manager.  One shared instance (``NULL_TELEMETRY``)
    serves the engine, every strategy and every backend layer, so the
    telemetry-off hot path costs a single attribute lookup per hook."""
    __slots__ = ()
    enabled = False

    def span(self, name: str):
        return _NULL_SPAN

    def generation(self, gen: int):
        return _NULL_SPAN

    def count(self, name: str, value: float) -> None:
        pass

    def start_run(self, engine) -> None:
        pass

    def end_round(self, gen: int, round_s: float, engine) -> None:
        pass

    def run_scope(self):
        return _NULL_SPAN

    def result(self, engine) -> None:
        return None


NULL_TELEMETRY = NullTelemetry()


class _Span:
    __slots__ = ("tel", "name", "t0", "ta")

    def __init__(self, tel: "Telemetry", name: str):
        self.tel = tel
        self.name = name

    def __enter__(self):
        # a host-side TraceMe: recorded only inside a profiler capture
        self.ta = jax.profiler.TraceAnnotation(self.name)
        self.ta.__enter__()
        self.tel._stack.append(self.name)
        self.t0 = time.perf_counter()
        return self

    def __exit__(self, *exc):
        dt = time.perf_counter() - self.t0
        tel = self.tel
        tel._book("/".join(tel._stack), dt)
        tel._stack.pop()
        self.ta.__exit__(*exc)
        return False


class Telemetry:
    """The live telemetry object of one engine.

    ``FedEngine`` owns exactly one (when ``RunConfig.telemetry`` is
    enabled), shares it with every backend layer (``obs.attach``) and
    drives the run lifecycle: ``start_run`` resets all state (run
    re-entrancy), ``run_scope`` brackets the run (compile listeners, the
    profiler capture), ``generation`` brackets one generation (step
    marker, capture window), ``span`` times a phase on the shared
    nesting stack, ``count`` adds to a round counter, ``end_round``
    assembles the round's ``RoundEvent`` and pushes it to the ring +
    sink, ``result`` returns the ``TelemetryResult`` stamped onto
    ``EngineResult``."""

    enabled = True

    def __init__(self, cfg: TelemetryConfig):
        self.cfg = cfg
        self.ring = MemorySink(cfg.ring)
        self.sink = (None if cfg.sink == "memory"
                     else make_sink(cfg.sink, ring=cfg.ring))
        self._stack: List[str] = []
        self._spans: Dict[str, float] = {}
        self._counts: Dict[str, int] = {}
        self._counters: Dict[str, float] = {}
        self._compiled: List[Tuple[float, float]] = []
        self._capturing = False
        self._tc_snap: Dict[str, int] = {}
        self._comm_snap: Dict[str, float] = {}
        self._peak_live = 0

    # -- lifecycle -----------------------------------------------------------

    def start_run(self, engine) -> None:
        """Reset per-run state; snapshot trace counts so pre-run traces
        (a backend reused across runs) are not booked to round 1."""
        self.ring.reset()
        self._stack = []
        self._spans = {}
        self._counts = {}
        self._counters = {}
        self._compiled = []
        self._peak_live = 0
        self._tc_snap = dict(self._trace_counts(engine))
        self._comm_snap = {f: 0.0 for f in COMM_FIELDS}

    def span(self, name: str) -> _Span:
        return _Span(self, name)

    def count(self, name: str, value: float) -> None:
        """Add ``value`` to this round's counter ``name``."""
        self._counters[name] = self._counters.get(name, 0.0) + value

    @contextlib.contextmanager
    def run_scope(self):
        """One ``run()``: the ``jax.monitoring`` listeners that book
        compiles are registered for its length only, and a capture the
        run left open (it raised inside the window) is stopped."""
        monitoring.register_event_time_span_listener(self._on_compile)
        monitoring.register_event_duration_secs_listener(self._on_cache_load)
        try:
            yield
        finally:
            monitoring.unregister_event_time_span_listener(self._on_compile)
            monitoring.unregister_event_duration_listener(
                self._on_cache_load)
            if self._capturing:
                self._stop_capture()

    @contextlib.contextmanager
    def generation(self, gen: int):
        """One generation under a ``StepTraceAnnotation("generation",
        step_num=gen)``, starting or stopping the ``profile_gens``
        capture at its edges."""
        first, count = self.cfg.profile_gens or (0, 0)
        if gen == first:
            self._start_capture()
        try:
            with jax.profiler.StepTraceAnnotation("generation",
                                                  step_num=gen):
                yield
        finally:
            if self._capturing and gen >= first + count - 1:
                self._stop_capture()

    def end_round(self, gen: int, round_s: float, engine) -> RoundEvent:
        """Assemble and emit this round's event, then reset the span
        accumulators for the next round."""
        tc = dict(self._trace_counts(engine))
        recompiles = {k: v - self._tc_snap.get(k, 0) for k, v in tc.items()
                      if v != self._tc_snap.get(k, 0)}
        self._tc_snap = tc
        comm = {}
        for f in COMM_FIELDS:
            v = float(getattr(engine.stats, f, 0.0))
            comm[f] = v - self._comm_snap.get(f, 0.0)
            self._comm_snap[f] = v
        event = RoundEvent(gen=gen, round_s=round_s,
                           spans=self._spans, span_counts=self._counts,
                           recompiles=recompiles,
                           gauges=self._gauges(engine), comm=comm,
                           counters=self._counters)
        self._spans = {}
        self._counts = {}
        self._counters = {}
        self._compiled = []
        self.ring.emit(event)
        if self.sink is not None:
            self.sink.emit(event)
        return event

    def result(self, engine) -> TelemetryResult:
        return TelemetryResult(events=list(self.ring.events),
                               trace_counts=dict(self._trace_counts(engine)))

    # -- internals -----------------------------------------------------------

    def _book(self, path: str, seconds: float) -> None:
        self._spans[path] = self._spans.get(path, 0.0) + seconds
        self._counts[path] = self._counts.get(path, 0) + 1

    def _child(self, *names: str) -> str:
        return "/".join(self._stack + list(names))

    def _on_compile(self, event, start_time, end_time, **_):
        """Book the part of one compile stage's interval that no stage
        booked before covers (stages nest; see ``COMPILE_EVENTS``)."""
        if event not in COMPILE_EVENTS:
            return
        hit = [(s, e) for s, e in self._compiled
               if s < end_time and e > start_time]
        covered = sum(min(e, end_time) - max(s, start_time) for s, e in hit)
        self._compiled = [iv for iv in self._compiled if iv not in hit]
        self._compiled.append((min([start_time] + [s for s, _ in hit]),
                               max([end_time] + [e for _, e in hit])))
        self._book(self._child("compile"), (end_time - start_time) - covered)

    def _on_cache_load(self, event, duration_secs, **_):
        if event == CACHE_LOAD_EVENT:
            self._book(self._child("compile", "cache_load"), duration_secs)

    def _start_capture(self) -> None:
        os.makedirs(self.cfg.profiler_dir, exist_ok=True)
        opts = jax.profiler.ProfileOptions()
        opts.python_tracer_level = 0
        jax.profiler.start_trace(self.cfg.profiler_dir,
                                 profiler_options=opts)
        self._capturing = True

    def _stop_capture(self) -> None:
        self._capturing = False
        jax.profiler.stop_trace()

    @staticmethod
    def _trace_counts(engine) -> Dict[str, int]:
        return getattr(innermost(engine.backend), "trace_counts", {})

    def _gauges(self, engine) -> Dict[str, Any]:
        if not self.cfg.gauges:
            return {}
        live = live_device_bytes()
        self._peak_live = max(self._peak_live, live)
        out: Dict[str, Any] = {
            "live_device_bytes": live,
            "peak_live_device_bytes": self._peak_live,
            "host_rss_bytes": host_rss_bytes(),
        }
        clients = getattr(engine, "clients", None)
        materialized = getattr(clients, "materialized", None)
        if materialized is not None:     # lazy ClientFleet only
            out["clients_materialized"] = materialized
            out["clients_cached"] = getattr(clients, "cached", None)
            out["fleet_hits"] = getattr(clients, "hits", None)
        cache_stats = getattr(innermost(engine.backend), "cache_stats", None)
        if cache_stats is not None:      # stacked (vmap/mesh) backends only
            out.update(cache_stats)
        return out
