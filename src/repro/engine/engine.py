"""FedEngine: one round loop for every federated NAS runtime.

The engine owns what is common to the paper's Algorithms 1/4 and the
offline baseline — participant sampling, the per-round lr schedule,
communication/compute accounting and the typed ``RoundReport`` history —
and delegates the rest to a ``Strategy`` (what happens inside a round) and
an ``ExecutionBackend`` (how client work is dispatched: ``"loop"`` for the
reference per-pair path, ``"vmap"`` for the vectorized one, ``"mesh"``
for the device-mesh-sharded one — see docs/architecture.md).

    engine = FedEngine(api, clients, RunConfig(backend="mesh"))
    result = engine.run()            # EngineResult
    history = result.history()       # legacy dict-of-lists view
"""
from __future__ import annotations

import time
from typing import Callable, Optional, Sequence, Union

import numpy as np

from repro.comm import CodecBackend, make_codec
from repro.core.double_sampling import sample_participants
from repro.core.supernet import SupernetAPI
from repro.data.pipeline import ClientDataset
from repro.engine.availability import ClientSimulator, RoundSim
from repro.engine.backends import ExecutionBackend, make_backend
from repro.engine.strategies import RealTimeNas, Strategy
from repro.engine.types import CommStats, EngineResult, RoundReport, \
    RunConfig
from repro.obs import NULL_TELEMETRY, InstrumentedBackend, Telemetry, attach
from repro.optim import round_decay


class FedEngine:
    """One round loop for every federated NAS runtime.

    Args:
      * ``api`` — the model family's ``SupernetAPI`` (init / loss /
        error-count / trained-mask / flops / payload as functions of a
        choice key).
      * ``clients`` — the ``ClientDataset`` population (pre-batched
        local train/test shards; ``weight`` = n_k for weighted
        averaging).
      * ``cfg`` — a ``RunConfig`` (defaults to ``RunConfig()``); see its
        docstring for every knob and unit.
      * ``strategy`` — what happens inside a round; defaults to
        ``RealTimeNas()`` (paper Algorithm 4).
      * ``backend`` — an execution backend name (``'loop' | 'vmap' |
        'mesh'``, overriding ``cfg.backend``) or an already-built
        ``ExecutionBackend`` instance (e.g. ``MeshBackend(...,
        mesh=make_production_mesh())``).  Unknown names raise here, at
        construction time.
    """

    def __init__(self, api: SupernetAPI, clients: Sequence[ClientDataset],
                 cfg: Optional[RunConfig] = None,
                 strategy: Optional[Strategy] = None,
                 backend: Union[str, ExecutionBackend, None] = None):
        self.api = api
        # indexable client collections (lists, lazy ClientFleet) are kept
        # as-is — list()-ing a million-client fleet would materialize it;
        # plain iterables are drained once
        if hasattr(clients, "__getitem__") and hasattr(clients, "__len__"):
            self.clients = clients
        else:
            self.clients = list(clients)
        self.cfg = cfg or RunConfig()
        self.strategy = strategy or RealTimeNas()
        if backend is None or isinstance(backend, str):
            self.backend = make_backend(backend or self.cfg.backend,
                                        api, self.clients, self.cfg)
        else:
            self.backend = backend
        # payload codecs (repro.comm): strategies read these for wire-byte
        # accounting; lossy codecs additionally wrap the execution backend
        # so encode->decode happens around every client train/eval
        self.uplink_codec = make_codec(self.cfg.uplink_codec)
        self.downlink_codec = make_codec(self.cfg.downlink_codec)
        if not (self.uplink_codec.is_identity
                and self.downlink_codec.is_identity):
            self.backend = CodecBackend(self.backend, self.uplink_codec,
                                        self.downlink_codec)
        # telemetry (repro.obs): only when RunConfig.telemetry is enabled
        # does the engine build a real Telemetry and wrap the backend —
        # the InstrumentedBackend goes OUTERMOST so its fill_train/eval
        # spans cover codec encode/decode, which nest beneath them.
        # Disabled runs keep the exact pre-subsystem object graph
        # (everything sees the shared no-op NULL_TELEMETRY).
        tcfg = self.cfg.telemetry
        if tcfg is not None and tcfg.enabled:
            self.telemetry = Telemetry(tcfg)
            attach(self.backend, self.telemetry)
            self.backend = InstrumentedBackend(self.backend, self.telemetry)
        else:
            self.telemetry = NULL_TELEMETRY
        self.rng = np.random.default_rng(self.cfg.seed)
        self.stats = CommStats()
        self.reports: list[RoundReport] = []
        # client-availability simulation (repro.engine.availability) —
        # constructed here so a bad availability_trace fails at engine
        # build time, and rebuilt per run() for re-entrancy
        self.sim = ClientSimulator(self.cfg.client_sim, len(self.clients))
        self.round_ctx: Optional[RoundSim] = None

    def run(self, callback: Optional[Callable[[int, RoundReport], None]]
            = None) -> EngineResult:
        """Run ``cfg.generations`` federated rounds and return an
        ``EngineResult`` (typed ``RoundReport`` history + ``CommStats``
        totals + strategy extras).  ``callback(gen, report)`` fires after
        every round.  Re-entrant: repeated calls reset all run state and
        reproduce the same seed-deterministic trajectory."""
        cfg = self.cfg
        # fresh run state so repeated run() calls are independent and
        # seed-reproducible (the legacy rt_enas.run was a pure function)
        self.rng = np.random.default_rng(cfg.seed)
        self.stats = CommStats()
        self.reports = []
        self.backend.dispatches = 0
        reset = getattr(self.backend, "reset", None)
        if reset is not None:        # CodecBackend: drop EF residuals
            reset()
        self.sim = ClientSimulator(cfg.client_sim, len(self.clients))
        tel = self.telemetry
        tel.start_run(self)
        # compile listeners and the profile_gens capture, when configured
        with tel.run_scope():
            with tel.span("init"):           # booked into generation 1
                self.strategy.setup(self)
            # perf_counter, not time.time(): wall-clock is not monotonic,
            # an NTP step mid-run would corrupt the recorded round_s
            t0 = t_prev = time.perf_counter()
            for gen in range(1, cfg.generations + 1):
                # the step marker runs from the first sample through
                # end_round; the user callback stays outside it
                with tel.generation(gen):
                    report = self._round(gen)
                    now = time.perf_counter()
                    report.wall_s = now - t0      # cumulative since run()
                    report.round_s = now - t_prev  # this round's delta
                    t_prev = now
                    self.reports.append(report)
                    tel.end_round(gen, report.round_s, self)
                if callback:
                    callback(gen, report)
        # a stale RoundSim must not leak into strategies driven manually
        # on this engine afterwards (they fall back to an inactive ctx)
        self.round_ctx = None
        return EngineResult(reports=self.reports, stats=self.stats,
                            extras=self.strategy.extras(self),
                            telemetry=tel.result(self))

    def _round(self, gen: int) -> RoundReport:
        """One generation: sampling, the availability draw, the
        strategy's round and the report's traffic columns."""
        cfg, tel = self.cfg, self.telemetry
        lr = float(round_decay(cfg.lr0, cfg.lr_decay, gen - 1))
        with tel.span("sample"):
            sampled = sample_participants(self.rng, len(self.clients),
                                          cfg.participation)
        # availability / dropout draw (sim RNG only — the search RNG
        # stream above is untouched by the simulation)
        with tel.span("availability"):
            ctx = self.sim.draw_round(sampled)
        self.round_ctx = ctx
        report = self.strategy.round(self, gen, ctx.participants, lr)
        report.down_gb = self.stats.down_bytes / 1e9
        report.up_gb = self.stats.up_bytes / 1e9
        report.train_passes = self.stats.client_train_passes
        if ctx.active:
            report.n_sampled = ctx.n_sampled
            report.n_available = len(ctx.participants)
            report.n_dropped = ctx.n_dropped
            report.n_survivors = ctx.n_survivors
            report.wasted_down_gb = self.stats.wasted_down_bytes / 1e9
        return report
