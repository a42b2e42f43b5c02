"""Pluggable client-execution backends for the federated engine.

A backend answers four questions for a strategy — *how* to run local SGD
and evaluation, never *what* to run (sampling, accounting and selection
live in the strategies / engine, so every backend sees the same inputs):

  * ``train_fill``   — train keys[i]'s sub-model on client group i from a
    shared master and fill-aggregate the uploads (Algorithm 3/4).
  * ``train_fedavg`` / ``train_fedavg_population`` — train one (or each)
    standalone model on every listed client and FedAvg per model
    (Algorithm 1 / the offline baseline).
  * ``eval_shared`` / ``eval_paired`` — weighted test error of K keys on a
    shared master, or of K (params, key) pairs.

``LoopBackend`` is the reference: one jitted dispatch per
(individual, client) pair, exactly the pre-engine semantics.
``VmapBackend`` stacks each same-shape client group into a ``ClientBatch``
and runs all population x client updates — and all 2N x participants
evaluations — in O(population) jitted dispatches per generation,
constant in the number of participating clients.  ``MeshBackend``
(``repro.engine.mesh_backend``) additionally shards the population axis
of those stacks over a jax device mesh.  All backends count
``dispatches`` so tests and benchmarks can assert those claims instead
of trusting them.

With ``RunConfig.fused`` (the default) the batched backends collapse
further, to a *constant* number of dispatches per generation: the whole
population's choice keys are stacked into one (P, num_blocks) device
array and a single jitted program per ``train_fill`` runs the local-SGD
scan, the per-group weighting and the Algorithm 3 partial sums for
every individual (master passed with ``donate_argnums`` off-CPU so the
per-generation master update reuses its buffers — see
``master_donation_safe``), while a single evaluation program takes the
master plus all stacked keys and returns the on-device wrong-count
vector, fetched with one ``jax.device_get`` per generation instead of
2N x buckets blocking ``int(...)`` syncs.  The shared program bodies
(``fill_bucket_partial``, ``eval_bucket_counts``, ...) live here;
``MeshBackend`` composes the same bodies with its ``shard_map``/``psum``
structure, so the fused sharded path is O(1) dispatches per generation.

Every backend routes Algorithm 3 through ``RunConfig.aggregate_backend``
identically: ``"xla"`` is the jnp reference, ``"pallas"`` the
``repro.kernels.fill_aggregate`` TPU kernel (interpreted on CPU).
Unknown values are rejected by ``RunConfig`` at construction time.

Payload codecs never appear in this module: when
``RunConfig.uplink_codec`` / ``downlink_codec`` select a lossy codec,
``FedEngine`` wraps whichever backend it built in
``repro.comm.backend.CodecBackend``, which applies encode->decode around
these train/eval entry points uniformly — so the dispatch math here (and
in ``mesh_backend``) stays codec-free and every backend sees identical
compressed inputs.

Client availability (``ClientSimConfig``) reaches every entry point as
an optional ``survivors`` set — the clients whose uploads actually
arrive this round.  The batched backends keep their program shapes
STATIC under dropout: dropped clients stay in the stacked arrays and are
masked out instead — for training their aggregation weight is zeroed
host-side (exactly the weight-0 padding-row mechanism, so
``fill_bucket_partial`` / ``fedavg_population_bucket`` need no new
arguments and a zeroed row contributes *exactly* nothing), with the
normalization total taken over survivors only; for evaluation an int32
``alive`` mask rides into ``eval_bucket_counts`` and multiplies the
per-client wrong counts (integer math — masking is exact).  The fused
dispatch count is therefore unchanged at any dropout rate.  The loop
backend simply skips dead clients, which the weight-0/masked paths
reproduce exactly.  ``survivors=None`` (the default) is the legacy
fully-synchronous path.
"""
from __future__ import annotations

import dataclasses
from typing import Any, List, Protocol, Sequence

import jax
import jax.numpy as jnp
import numpy as np

from repro.core.aggregate import fedavg, fill_aggregate_stacked, \
    fill_partial
from repro.core.federated import client_update_fn, eval_count_fn, \
    weighted_test_error
from repro.core.supernet import SupernetAPI
from repro.data.pipeline import ClientBatch, ClientDataset, shape_buckets
from repro.engine.types import RunConfig
from repro.obs import NULL_TELEMETRY, traced

Params = Any


def master_donation_safe(cfg: RunConfig) -> bool:
    """Whether a fused ``train_fill`` may pass the master pytree with
    ``donate_argnums`` (reusing its buffers for the updated master).

    Donation invalidates the caller's master after the dispatch.  Every
    strategy overwrites its master with ``train_fill``'s return value,
    so the only reader of the *old* buffers is ``CodecBackend``: with a
    lossy uplink codec it re-reads the downlinked master to form the
    uplink delta (``raw - sent_down``) after the inner call.  Hence:
    donation is safe iff the uplink codec is the identity.  (The jit
    donation itself is additionally gated on a non-CPU jax backend at
    construction time — CPU XLA cannot reuse donated buffers and would
    warn on every dispatch.)"""
    from repro.comm import make_codec
    return make_codec(cfg.uplink_codec).is_identity


# ---------------------------------------------------------------------------
# Fused-generation program bodies (shared by VmapBackend and MeshBackend)
# ---------------------------------------------------------------------------
#
# Each body consumes ONE shape bucket of group-major stacked arrays (see
# StackedClientBase._group_bucket_arrays) and keeps every choice key a
# traced *scalar* via lax.scan, so lax.switch in the model forward stays
# a real branch (vmapping the key axis would lower to compute-all-
# branches-and-select; benchmarks/fed_nas.py re-measures that trade per
# phase — see docs/architecture.md "Fused generations").  MeshBackend
# wraps the same bodies in shard_map (+ psum for train), which is what
# keeps the loop/vmap/mesh float32 reduction orders aligned.

def fill_bucket_partial(upd, mask_fn, master, keys, xb, yb, w, lr):
    """Fused local SGD + Algorithm 3 partial sum over one shape bucket.

    ``keys`` (G, num_blocks) int32; ``xb``/``yb`` (G, S, nbat, B, ...);
    ``w`` (G, S) float32 globally normalized (0 = padding).  Scans over
    the G groups; per group, scans local SGD over the S clients and
    reduces with ``aggregate.fill_partial`` — the same expression the
    non-fused stacked aggregator uses.  Returns the float32 partial-sum
    tree (callers add buckets and cast back to the master dtypes)."""

    def per_group(acc, inp):
        key, gx, gy, gw = inp

        def per_client(_, c):
            return None, upd(master, key, c[0], c[1], lr)

        # named_scope labels (profiler captures / HLO dumps only — they
        # never change numerics): the local-SGD scan vs the Algorithm 3
        # partial-sum reduction inside the fused fill program
        with jax.named_scope("local_sgd"):
            outs = jax.lax.scan(per_client, None, (gx, gy))[1]
        with jax.named_scope("fill_aggregate"):
            keys_s = jnp.broadcast_to(key, (gw.shape[0],) + key.shape)
            masks = jax.vmap(mask_fn)(outs, keys_s)
            part = fill_partial(master, outs, masks, gw)
            return jax.tree.map(jnp.add, acc, part), None

    zeros = jax.tree.map(lambda x: jnp.zeros(x.shape, jnp.float32), master)
    return jax.lax.scan(per_group, zeros, (keys, xb, yb, w))[0]


def train_bucket_uploads(upd, master, keys, xb, yb, lr):
    """Fused local SGD over one bucket, uploads returned stacked
    (G, S, ...) — the ``aggregate_backend='pallas'`` route, where
    Algorithm 3 runs in the ``repro.kernels.fill_aggregate`` kernel
    outside this program."""

    def per_group(_, inp):
        key, gx, gy = inp

        def per_client(__, c):
            return None, upd(master, key, c[0], c[1], lr)

        return None, jax.lax.scan(per_client, None, (gx, gy))[1]

    return jax.lax.scan(per_group, None, (keys, xb, yb))[1]


def _tiled_count(ev, params, key, xb, yb, alive, tile):
    """Wrong count of one (params, key) pair over a stacked test bucket,
    with the client axis consumed ``tile`` shards per scan step through
    an inner ``vmap`` (forward-only compute is cheap enough for moderate
    batching to pay — the same trade ``RunConfig.vmap_eval_tile`` makes
    on the non-fused path).  ``alive`` is the (S,) int32 survivor mask
    multiplying each client's count (1s when the availability simulation
    is off).  Counts are integers, so tiling and masking are both
    exact."""
    m = xb.shape[0]
    tile = max(1, min(tile, m))
    full = (m // tile) * tile
    tile_ev = jax.vmap(ev, in_axes=(None, None, 0, 0))
    acc = jnp.zeros((), jnp.int32)
    # named_scope labels (profiler captures / HLO dumps only — they
    # never change numerics) for the masked client-axis count scans
    if full:
        fx = xb[:full].reshape((full // tile, tile) + xb.shape[1:])
        fy = yb[:full].reshape((full // tile, tile) + yb.shape[1:])
        fa = alive[:full].reshape((full // tile, tile))

        def tiles(a, c):
            return a + jnp.sum(c[2] * tile_ev(params, key, c[0], c[1])), None

        with jax.named_scope("eval_count_tiles"):
            acc = jax.lax.scan(tiles, acc, (fx, fy, fa))[0]
    if m > full:
        def tail(a, c):
            return a + c[2] * ev(params, key, c[0], c[1]), None

        with jax.named_scope("eval_count_tail"):
            acc = jax.lax.scan(tail, acc,
                               (xb[full:], yb[full:], alive[full:]))[0]
    return acc


def eval_bucket_counts(ev, params, keys, xb, yb, alive, tile=1):
    """Wrong counts of every key on one shared master over one stacked
    test bucket: ``keys`` (K, num_blocks) -> (K,) int32 on device.  The
    key axis is consumed by ``lax.scan`` (scalar keys keep ``lax.switch``
    a real branch); the client axis is tiled (``_tiled_count``) and
    masked by the (S,) int32 ``alive`` survivor vector."""

    def per_key(_, key):
        return None, _tiled_count(ev, params, key, xb, yb, alive, tile)

    return jax.lax.scan(per_key, None, keys)[1]


def eval_paired_bucket_counts(ev, ps, keys, xb, yb, alive, tile=1):
    """``eval_bucket_counts`` for (params, key) pairs: every ``ps`` leaf
    carries a leading (K,) axis aligned with ``keys``."""

    def per_pair(_, inp):
        p, key = inp
        return None, _tiled_count(ev, p, key, xb, yb, alive, tile)

    return jax.lax.scan(per_pair, None, (ps, keys))[1]


def fedavg_population_bucket(upd, ps, keys, xb, yb, wn, lr):
    """Per-individual FedAvg partial sums over one train bucket: ``ps``
    leaves (P, ...), ``keys`` (P, nb); ``xb``/``yb`` (S, nbat, B, ...)
    and ``wn`` (S,) normalized weights shared by every individual.
    Mirrors the non-fused ``scan_update_avg`` (stacked outs, one
    weighted ``jnp.sum``) so reduction order matches across paths."""

    def per_ind(_, inp):
        p, key = inp

        def per_client(__, c):
            return None, upd(p, key, c[0], c[1], lr)

        outs = jax.lax.scan(per_client, None, (xb, yb))[1]

        def avg(x):
            wr = wn.reshape((-1,) + (1,) * (x.ndim - 1))
            return jnp.sum(wr * x.astype(jnp.float32), axis=0)

        return None, jax.tree.map(avg, outs)

    return jax.lax.scan(per_ind, None, (ps, keys))[1]


def accumulate_parts(parts):
    """Sum an iterable of identically-shaped pytrees (a bare array is a
    one-leaf pytree) — the bucket combiner of every fused program."""
    acc = None
    for part in parts:
        acc = part if acc is None else jax.tree.map(jnp.add, acc, part)
    return acc


def cast_like(tree, ref):
    """Cast every leaf of the float32 accumulator back to ``ref``'s
    dtypes (the fused programs' final step — with a donated master, the
    output reuses ``ref``'s buffers)."""
    return jax.tree.map(lambda a, r: a.astype(r.dtype), tree, ref)


class ExecutionBackend(Protocol):
    """The dispatch contract every backend implements.

    ``dispatches`` counts jitted device dispatches issued so far (the
    scaling claims in docs/architecture.md are asserted against it).
    All ``keys`` are (num_blocks,) int32 choice keys; ``client_ids`` /
    ``groups`` index into the backend's client list; ``lr`` is the
    round's learning rate.  ``survivors`` is ``None`` (every client
    completes — the legacy path) or the set of client ids whose uploads
    arrive this round (``ClientSimConfig`` dropout): non-survivors must
    contribute nothing to aggregation or error counts, with weights
    renormalized over survivors.  Returned parameters are full pytrees;
    ``eval_*`` return (len(keys),) float64 weighted test-error rates in
    [0, 1] over the surviving participants."""

    name: str
    dispatches: int

    def train_fill(self, master: Params, keys: Sequence[np.ndarray],
                   groups: Sequence[np.ndarray], lr: float,
                   survivors=None) -> Params:
        """Train keys[g] on client group g from the shared master and
        fill-aggregate the surviving uploads into the new master
        (Algorithm 3/4); groups may be empty (their individuals'
        blocks are filled from the previous master).  Callers must
        treat ``master`` as consumed — fused backends may donate its
        buffers to the returned update (``master_donation_safe``)."""
        ...

    def train_fedavg(self, params: Params, key: np.ndarray,
                     client_ids: np.ndarray, lr: float,
                     survivors=None) -> Params:
        """One FedAvg round of ``key``'s standalone model over every
        listed client (Algorithm 1)."""
        ...

    def train_fedavg_population(self, params_list: Sequence[Params],
                                keys: Sequence[np.ndarray],
                                client_ids: np.ndarray,
                                lr: float, survivors=None) -> List[Params]:
        """``train_fedavg`` for each (params, key) pair — every client
        trains every individual (the offline baseline)."""
        ...

    def eval_shared(self, params: Params, keys: Sequence[np.ndarray],
                    client_ids: np.ndarray, survivors=None) -> np.ndarray:
        """Weighted test-error rate of every key on one shared master."""
        ...

    def eval_paired(self, params_list: Sequence[Params],
                    keys: Sequence[np.ndarray],
                    client_ids: np.ndarray, survivors=None) -> np.ndarray:
        """Weighted test-error rate of every (params, key) pair."""
        ...


# ---------------------------------------------------------------------------
# Reference backend: one dispatch per pair
# ---------------------------------------------------------------------------

class LoopBackend:
    """Reference execution: one jitted dispatch per (individual, client)
    pair — exactly the pre-engine (per-pair Python loop) semantics that
    the batched backends are tested against.  Algorithm 3 routes through
    ``fill_aggregate_stacked(backend=cfg.aggregate_backend)``, one chunk
    per client group."""

    name = "loop"
    # shared no-op unless FedEngine attaches a real Telemetry (repro.obs)
    telemetry = NULL_TELEMETRY

    def __init__(self, api: SupernetAPI, clients: Sequence[ClientDataset],
                 cfg: RunConfig):
        self.api = api
        self.clients = clients
        self.cfg = cfg
        # the same programs make_client_update/make_evaluator build, with
        # a per-program trace counter + named_scope label around the body
        # (repro.obs.traced — tracing runs the Python wrapper, cached
        # dispatches don't, so the counts are recompile truth)
        self.trace_counts: dict = {}
        self.update = jax.jit(traced(
            "client_update", self.trace_counts,
            client_update_fn(api, cfg.local_epochs, cfg.momentum)))
        self.evaluate = jax.jit(traced(
            "evaluator", self.trace_counts, eval_count_fn(api)))
        self.dispatches = 0

    @staticmethod
    def _alive(survivors, cid) -> bool:
        return survivors is None or int(cid) in survivors

    def train_fill(self, master, keys, groups, lr, survivors=None):
        # Algorithm 3 is reduced per group and the group partials summed
        # in group order — the batched backends' order (fill_partial), so
        # the reference and the fused programs agree to the last ulp of
        # the aggregation and SGD has no reduction-order drift to amplify
        chunks = []
        for key, group in zip(keys, groups):
            jkey = np.asarray(key, np.int32)
            ups, ws = [], []
            for cid in group:
                c = self.clients[int(cid)]
                if self._alive(survivors, cid):
                    xb, yb = c.train
                    ups.append(self.update(master, jkey, xb, yb, lr))
                    ws.append(c.weight)
                    self.dispatches += 1
                else:
                    # dropped: its upload never arrives, so its row has
                    # weight 0 (the master stands in) — the group keeps
                    # the rows the batched backends reduce
                    ups.append(master)
                    ws.append(0.0)
            if any(ws):
                chunks.append((jax.tree.map(lambda *xs: jnp.stack(xs), *ups),
                               np.tile(jkey, (len(ups), 1)),
                               np.asarray(ws, np.float32)))
        if not chunks:
            return master
        self.dispatches += len(chunks)
        return fill_aggregate_stacked(master, chunks,
                                      mask_fn=self.api.trained_mask,
                                      backend=self.cfg.aggregate_backend)

    def train_fedavg(self, params, key, client_ids, lr, survivors=None):
        jkey = np.asarray(key, np.int32)
        uploads = []
        for cid in client_ids:
            if not self._alive(survivors, cid):
                continue
            c = self.clients[int(cid)]
            xb, yb = c.train
            uploads.append((self.update(params, jkey, xb, yb, lr), c.weight))
            self.dispatches += 1
        if not uploads:
            return params
        self.dispatches += 1
        return fedavg(uploads)

    def train_fedavg_population(self, params_list, keys, client_ids, lr,
                                survivors=None):
        return [self.train_fedavg(p, k, client_ids, lr, survivors=survivors)
                for p, k in zip(params_list, keys)]

    def eval_shared(self, params, keys, client_ids, survivors=None):
        part = [self.clients[int(i)] for i in client_ids
                if self._alive(survivors, i)]
        if not part:                   # nobody evaluated: pessimistic 1.0
            return np.ones(len(keys))
        errs = []
        for k in keys:
            errs.append(weighted_test_error(
                self.evaluate, params, np.asarray(k, np.int32), part))
            self.dispatches += len(part)
        return np.asarray(errs)

    def eval_paired(self, params_list, keys, client_ids, survivors=None):
        part = [self.clients[int(i)] for i in client_ids
                if self._alive(survivors, i)]
        if not part:                   # nobody evaluated: pessimistic 1.0
            return np.ones(len(keys))
        errs = []
        for p, k in zip(params_list, keys):
            errs.append(weighted_test_error(
                self.evaluate, p, np.asarray(k, np.int32), part))
            self.dispatches += len(part)
        return np.asarray(errs)


# ---------------------------------------------------------------------------
# Shared stacking/caching for the batched (vmap, mesh) backends
# ---------------------------------------------------------------------------

class StackedClientBase:
    """Host-side stacking, bucketing and caching shared by the batched
    execution backends (``VmapBackend``, ``MeshBackend``): stack-on-demand
    stacked train-shard stores keyed by the round's sampled clients,
    per-group gathers from them, and a memoized stacked test set per
    participant set.  Only sampled clients are ever stacked (or, with a
    lazy ``ClientFleet``, even materialized) — device memory scales with
    participation, never fleet size.  Subclasses implement the
    ``ExecutionBackend`` protocol on top."""

    # shared no-op unless FedEngine attaches a real Telemetry (repro.obs)
    telemetry = NULL_TELEMETRY

    def __init__(self, api: SupernetAPI, clients: Sequence[ClientDataset],
                 cfg: RunConfig):
        self.api = api
        self.clients = clients
        self.cfg = cfg
        self._test_cache = {}
        self._train_cache = {}
        self.dispatches = 0
        # per-jitted-program trace counts (repro.obs.traced) and LRU
        # hit/miss counters for the stacked-store caches — read by the
        # telemetry round gauges, free when telemetry is off
        self.trace_counts: dict = {}
        self.cache_stats = {"train_store_hits": 0, "train_store_misses": 0,
                            "test_stack_hits": 0, "test_stack_misses": 0}

    def _stack(self, client_ids, split):
        return ClientBatch.stack([self.clients[int(i)] for i in client_ids],
                                 split=split)

    def _group_batches(self, client_ids, split):
        """Yield ClientBatches for one client group, bucketed by shape."""
        shapes = [(self.clients[int(i)].train if split == "train"
                   else self.clients[int(i)].test)[0].shape
                  for i in client_ids]
        for idxs in shape_buckets(shapes):
            yield self._stack([client_ids[i] for i in idxs], split)

    def _train_store(self, client_ids):
        """Device-resident stacked train shards for ``client_ids`` ONLY:
        [(cid -> row, xb, yb)] per shape bucket, built on demand and
        kept in a size-2 LRU keyed by the canonical (sorted,
        deduplicated) id tuple — the same policy as ``_test_batches``.
        Stacking just the round's sampled clients is what keeps device
        memory proportional to participation x population rather than
        ``num_clients`` (and what lets a lazy ``ClientFleet`` leave the
        rest of a 10^6-client fleet unmaterialized); shards are
        immutable, so entries never go stale, full participation hits
        the same key every round, and alternating participant sets keep
        both LRU slots live."""
        key = tuple(sorted({int(i) for i in client_ids}))
        cache = self._train_cache
        if key in cache:
            cache[key] = cache.pop(key)      # refresh recency (true LRU)
            self.cache_stats["train_store_hits"] += 1
        else:
            self.cache_stats["train_store_misses"] += 1
            if len(cache) >= 2:
                cache.pop(next(iter(cache)))  # evict least-recently-used
            # a miss is the round's host->device download of the sampled
            # clients' train shards — the telemetry "download" phase
            with self.telemetry.span("download"):
                shards = [self.clients[i].train for i in key]
                store = []
                for idxs in shape_buckets([s[0].shape for s in shards]):
                    xb = jnp.stack([jnp.asarray(shards[i][0])
                                    for i in idxs])
                    yb = jnp.stack([jnp.asarray(shards[i][1])
                                    for i in idxs])
                    store.append(({key[i]: row
                                   for row, i in enumerate(idxs)}, xb, yb))
                cache[key] = store
            self._count_staged(a for _, xb, yb in store for a in (xb, yb))
        return cache[key]

    def _count_staged(self, arrays) -> None:
        """Book the bytes of freshly staged shard arrays on the round's
        ``staged_bytes`` counter (computed only with telemetry on)."""
        if self.telemetry.enabled:
            self.telemetry.count("staged_bytes",
                                 float(sum(a.nbytes for a in arrays)))

    def _launch(self, program, *args):
        """Call one jitted program under the telemetry ``launch`` span —
        from call to return, which is the enqueue and not the device
        work — and count the dispatch."""
        with self.telemetry.span("launch"):
            out = program(*args)
        self.dispatches += 1
        return out

    def _client_weight(self, cid, survivors) -> float:
        """A client's aggregation weight this round: 0 for dropped
        clients, so they stay in the static stacked shapes but
        contribute exactly nothing (the weight-0 padding mechanism)."""
        cid = int(cid)
        if survivors is not None and cid not in survivors:
            return 0.0
        return self.clients[cid].weight

    def _survivor_total(self, client_ids, survivors) -> float:
        """Sum of surviving weights — the renormalization total."""
        return float(sum(self._client_weight(c, survivors)
                         for c in client_ids))

    def _group_train_gather(self, client_ids, survivors=None, store=None):
        """Yield (xb, yb, weights, num_shards) per shape bucket for one
        client group, gathered from ``store`` (the round's sampled-client
        stack — built from ``client_ids`` themselves when not passed;
        callers spanning several groups pass the store once so every
        group gathers from the same round-level stack).  Dropped clients
        ride at weight 0."""
        if store is None:
            store = self._train_store(client_ids)
        for pos, xb, yb in store:
            sel = [int(i) for i in client_ids if int(i) in pos]
            if not sel:
                continue
            rows = jnp.asarray([pos[i] for i in sel], jnp.int32)
            w = np.asarray([self._client_weight(i, survivors) for i in sel],
                           np.float32)
            yield xb[rows], yb[rows], w, len(sel)

    def _test_batches(self, client_ids):
        """Memoized test-shard stacks: shards are immutable, and the
        pooled wrong/total error is order-invariant, so the ids can be
        canonicalized (sorted) and the stack built — and placed on
        device — once per participant set instead of once per key per
        generation.  Size-2 LRU (hits refresh recency): full
        participation hits every round, alternating participant sets
        keep both entries live, and partial participation — a fresh set
        each round — never pins more than two stacked copies of the
        test data."""
        key = tuple(sorted(int(i) for i in client_ids))
        cache = self._test_cache
        if key in cache:
            cache[key] = cache.pop(key)      # refresh recency (true LRU)
            self.cache_stats["test_stack_hits"] += 1
        else:
            self.cache_stats["test_stack_misses"] += 1
            if len(cache) >= 2:
                cache.pop(next(iter(cache)))  # evict least-recently-used
            with self.telemetry.span("download"):
                cache[key] = [
                    dataclasses.replace(cb, xb=self._place_test(cb.xb),
                                        yb=self._place_test(cb.yb))
                    for cb in self._group_batches(key, "test")]
            self._count_staged(a for cb in cache[key] for a in (cb.xb,
                                                                 cb.yb))
        return cache[key]

    def _eval_inputs(self, client_ids, survivors):
        """The evaluation's staged inputs, under the ``stage`` span: the
        participants' test stacks, per stack the survivor mask, the
        surviving sample total, and the (xb, yb, alive) shards the
        fused programs take."""
        with self.telemetry.span("stage"):
            batches = self._test_batches(client_ids)
            masks = self._alive_masks(batches, survivors)
            shards = tuple((cb.xb, cb.yb, m) for cb, m in zip(batches, masks))
            return batches, masks, self._alive_total(batches, masks), shards

    def _place_test(self, arr):
        """Device placement for the cached test stacks; ``MeshBackend``
        overrides with an explicitly mesh-replicated put so the stack is
        transferred once per participant set, not once per dispatch."""
        return jnp.asarray(arr)

    @staticmethod
    def _alive_masks(batches, survivors):
        """Per test bucket, the (S,) int32 survivor mask the masked eval
        bodies consume (all-ones when ``survivors`` is None)."""
        if survivors is None:
            return [np.ones(cb.num_shards, np.int32) for cb in batches]
        return [np.asarray([1 if int(c) in survivors else 0
                            for c in cb.client_ids], np.int32)
                for cb in batches]

    @staticmethod
    def _alive_total(batches, masks) -> int:
        """Pooled test-sample count over surviving clients — the error
        denominator matching the masked counts."""
        return int(sum(int(m.sum()) * cb.samples_per_shard
                       for cb, m in zip(batches, masks)))

    def _rates(self, counts, total, n_keys):
        """One ``jax.device_get`` per generation: the on-device
        wrong-count vector -> pooled error rates of the first ``n_keys``
        keys (the rest is mesh padding) over ``total`` surviving test
        samples.  ``total == 0`` (nobody evaluated) is pessimistic 1.0,
        never a perfect score — the same convention the strategies and
        the loop backend use.  The blocking fetch is the telemetry
        ``host_fetch`` phase — with fused eval it is where the host
        actually waits on the generation's device work."""
        if total == 0:
            return np.ones(n_keys)
        with self.telemetry.span("host_fetch"):
            wrong = np.asarray(jax.device_get(counts), np.int64)
        return wrong[:n_keys] / total

    def _group_bucket_arrays(self, keys, groups, total, pad_groups=0,
                             place=jnp.asarray, survivors=None,
                             store=None):
        """Per shape bucket of the round's sampled-client train store
        (built from the union of ``groups`` when ``store`` is not
        passed), the group-major stacked arrays the fused / sharded fill
        programs consume:
        (keys (Gp, nb) int32, xb (Gp, S, nbat, B, ...), yb, w (Gp, S)
        float32 normalized by ``total``), with the G groups padded to
        Gp = G + ``pad_groups`` and ragged groups padded to S clients —
        all padding at weight 0, so it contributes exactly nothing.
        Dropped clients (``survivors``) ride the same mechanism: they
        keep their row — the stacked shapes stay static under any
        dropout rate — but at weight 0 and with ``total`` summed over
        survivors only.  ``place`` puts each array on device (the mesh
        backend shards the leading axis here); the keys array is placed
        once and shared by every bucket."""
        out = []
        g_n = len(groups)
        keys_arr = np.zeros((g_n + pad_groups, self.api.num_blocks),
                            np.int32)
        keys_arr[:g_n] = np.stack([np.asarray(k, np.int32) for k in keys])
        karr = place(keys_arr)       # one transfer, shared by buckets
        if store is None:
            store = self._train_store([c for g in groups for c in g])
        for pos, xb_all, yb_all in store:
            entries = [[(pos[int(c)], self._client_weight(c, survivors))
                        for c in g if int(c) in pos] for g in groups]
            s_max = max((len(e) for e in entries), default=0)
            if s_max == 0:
                continue
            rows = np.zeros((g_n + pad_groups, s_max), np.int32)
            w = np.zeros((g_n + pad_groups, s_max), np.float32)
            for g, e in enumerate(entries):
                if not e:
                    continue
                rows[g, :len(e)] = [row for row, _ in e]
                # normalize exactly as fill_aggregate_stacked does (f32
                # weight vector / f64 total) — a 1-ulp difference here
                # amplifies over generations of SGD
                w[g, :len(e)] = np.asarray([wt for _, wt in e],
                                           np.float32) / total
            out.append((karr, place(xb_all[rows]), place(yb_all[rows]),
                        place(w)))
        return out

    def _pallas_fill(self, master, buckets, outs):
        """Algorithm 3 through the ``fill_aggregate`` kernel over the
        stacked uploads ``outs`` (one (Gp, S, ...) tree per bucket of
        ``buckets``): one kernel call per group that has surviving
        weight, partials summed bucket by bucket and group by group —
        the order of ``fill_bucket_partial`` and of the loop reference,
        so the routes differ only by the kernel's own row sum."""
        chunks = []
        for (keys_a, _, _, w), out in zip(buckets, outs):
            keys_np, w_np = np.asarray(keys_a), np.asarray(w)
            for g in np.flatnonzero(w_np.any(axis=1)):
                chunks.append((jax.tree.map(lambda x: x[g], out),
                               np.tile(keys_np[g], (w_np.shape[1], 1)),
                               w_np[g]))
        master = fill_aggregate_stacked(master, chunks,
                                        mask_fn=self.api.trained_mask,
                                        backend="pallas", total=1.0)
        self.dispatches += len(chunks)
        return master

    def train_fedavg(self, params, key, client_ids, lr, survivors=None):
        """Algorithm 1 for one model == the population path at P = 1."""
        return self.train_fedavg_population([params], [key], client_ids,
                                            lr, survivors=survivors)[0]


# ---------------------------------------------------------------------------
# Vectorized backend: O(#shape-buckets) dispatches per call
# ---------------------------------------------------------------------------

class VmapBackend(StackedClientBase):
    """Vectorized execution over ``ClientBatch``-stacked shards.

    Exploits the double-sampling structure: every client in group g
    trains/evaluates the *same* choice key, so the key stays a scalar
    argument and XLA compiles exactly the selected-branch program of the
    loop backend.  (Batching the key through ``lax.switch`` would lower
    to computing all branches and selecting — a 3-4x compute blowup that
    no dispatch saving repays; measured on this repo's CNN supernet.)

    Within a dispatch the stacked client axis is consumed by
    ``lax.scan`` — per-iteration working set stays cache-sized, unlike a
    full client-axis ``vmap`` whose batched convolutions stream memory —
    with an optional inner ``vmap`` tile for evaluation
    (``RunConfig.vmap_eval_tile``), where the forward-only compute is
    cheap enough for moderate batching to pay.

    Per generation the non-fused path issues O(population) dispatches —
    constant in the number of participating clients, the axis that
    actually scales — instead of the loop backend's
    O(population x clients).  With ``cfg.fused`` (the default) the whole
    generation collapses further into one jitted program per train/eval
    call (O(1) dispatches per generation; the program still loops shape
    buckets *inside* the dispatch): the population's keys are stacked to
    (P, num_blocks) and consumed by the shared bucket bodies above, the
    master is donated off-CPU when ``master_donation_safe``, and
    evaluation returns one on-device count vector per generation instead
    of a blocking ``int(...)`` per key x tile.
    """

    name = "vmap"

    def __init__(self, api: SupernetAPI, clients: Sequence[ClientDataset],
                 cfg: RunConfig):
        super().__init__(api, clients, cfg)
        upd = client_update_fn(api, cfg.local_epochs, cfg.momentum)
        ev = eval_count_fn(api)
        mask_fn = api.trained_mask
        self.donate_master = (cfg.fused and master_donation_safe(cfg)
                              and jax.default_backend() != "cpu")

        # -- fused-generation programs (cfg.fused): one dispatch per call
        def fused_fill(master, buckets, lr):
            return cast_like(accumulate_parts(
                fill_bucket_partial(upd, mask_fn, master, keys, xb, yb,
                                    w, lr)
                for keys, xb, yb, w in buckets), master)

        def fused_uploads(master, buckets, lr):
            return tuple(train_bucket_uploads(upd, master, keys, xb, yb, lr)
                         for keys, xb, yb, _ in buckets)

        def fused_eval_shared(params, keys, shards):
            return accumulate_parts(
                eval_bucket_counts(ev, params, keys, xb, yb, alive,
                                   tile=cfg.vmap_eval_tile)
                for xb, yb, alive in shards)

        def fused_eval_paired(ps, keys, shards):
            return accumulate_parts(
                eval_paired_bucket_counts(ev, ps, keys, xb, yb, alive,
                                          tile=cfg.vmap_eval_tile)
                for xb, yb, alive in shards)

        def fused_fedavg(ps, keys, buckets, lr):
            return cast_like(accumulate_parts(
                fedavg_population_bucket(upd, ps, keys, xb, yb, wn, lr)
                for xb, yb, wn in buckets), ps)

        # every jitted program is wrapped by repro.obs.traced: each trace
        # bumps self.trace_counts[name] (the recompile counter telemetry
        # reports per round — "fused programs trace once per run" is a
        # tested invariant) and labels the program with jax.named_scope
        tc = self.trace_counts
        self._fused_fill = jax.jit(
            traced("fused_fill", tc, fused_fill),
            donate_argnums=(0,) if self.donate_master else ())
        self._fused_uploads = jax.jit(traced("fused_uploads", tc,
                                             fused_uploads))
        self._fused_eval_shared = jax.jit(traced("fused_eval_shared", tc,
                                                 fused_eval_shared))
        self._fused_eval_paired = jax.jit(traced("fused_eval_paired", tc,
                                                 fused_eval_paired))
        self._fused_fedavg = jax.jit(traced("fused_fedavg", tc,
                                            fused_fedavg))

        def scan_update(params, key, xb, yb, lr):
            # xb/yb: (L, nb, B, ...) -> stacked updated params (L, ...)
            def one(_, shard):
                return None, upd(params, key, shard[0], shard[1], lr)
            return jax.lax.scan(one, None, (xb, yb))[1]

        def scan_update_avg(params, key, xb, yb, lr, wnorm):
            # fused local SGD + weighted client average -> float32 partials
            outs = scan_update(params, key, xb, yb, lr)

            def avg(x):
                w = wnorm.reshape((-1,) + (1,) * (x.ndim - 1))
                return jnp.sum(w * x.astype(jnp.float32), axis=0)

            return jax.tree.map(avg, outs)

        def eval_tiles(params, key, xb, yb, alive):
            # xb/yb: (T, tile, nb, B, ...), alive (T, tile) int32 survivor
            # mask -> total error count over surviving clients
            tile_ev = jax.vmap(ev, in_axes=(None, None, 0, 0))

            def one(acc, shard):
                return acc + jnp.sum(shard[2] * tile_ev(params, key,
                                                        shard[0],
                                                        shard[1])), None
            return jax.lax.scan(one, jnp.zeros((), jnp.int32),
                                (xb, yb, alive))[0]

        self._scan_update = jax.jit(traced("scan_update", tc, scan_update))
        self._scan_update_avg = jax.jit(traced("scan_update_avg", tc,
                                               scan_update_avg))
        self._eval_tiles = jax.jit(traced("eval_tiles", tc, eval_tiles))

    # -- protocol -----------------------------------------------------------

    def train_fill(self, master, keys, groups, lr, survivors=None):
        if self.cfg.fused:
            return self._train_fill_fused(master, keys, groups, lr,
                                          survivors)
        chunks = []
        # one sampled-client stack for the whole generation — every group
        # gathers from it, so the LRU sees a single round-level key
        all_ids = [int(c) for g in groups for c in g]
        store = self._train_store(all_ids) if all_ids else None
        for key, group in zip(keys, groups):
            if len(group) == 0:
                continue
            if survivors is not None and \
                    not any(int(c) in survivors for c in group):
                continue    # fully-dropped group: its weight-0 rows would
                # contribute exactly nothing — skip the training dispatch
            jkey = np.asarray(key, np.int32)
            for xb, yb, w, n in self._group_train_gather(group, survivors,
                                                         store=store):
                out = self._launch(self._scan_update, master, jkey, xb, yb,
                                   lr)
                chunks.append((out, np.tile(jkey, (n, 1)), w))
        if not chunks or not any(np.any(w) for _, _, w in chunks):
            return master              # nobody survived: master untouched
        # per-group stacked uploads feed the batched fill directly (one
        # dispatch per chunk; concatenating first would duplicate every
        # upload on device just to save the partial-sum adds)
        master = fill_aggregate_stacked(master, chunks,
                                        mask_fn=self.api.trained_mask,
                                        backend=self.cfg.aggregate_backend)
        self.dispatches += len(chunks)
        return master

    def _train_fill_fused(self, master, keys, groups, lr, survivors=None):
        groups = [np.asarray(g) for g in groups]
        total = self._survivor_total([c for g in groups for c in g],
                                     survivors)
        if total == 0.0:
            return master
        with self.telemetry.span("stage"):
            buckets = tuple(self._group_bucket_arrays(keys, groups, total,
                                                      survivors=survivors))
            lr = jnp.float32(lr)
        if not buckets:
            return master
        if self.cfg.aggregate_backend == "pallas":
            # partial fusion: one program for the whole population's
            # local SGD, then Algorithm 3 through the Pallas kernel
            outs = self._launch(self._fused_uploads, master, buckets, lr)
            return self._pallas_fill(master, buckets, outs)
        # donated master: the caller's buffers are reused for the update
        return self._launch(self._fused_fill, master, buckets, lr)

    def _fedavg_from_batches(self, params, jkey, batches, total, lr):
        acc = None
        for xb, yb, w, _ in batches:
            part = self._launch(self._scan_update_avg, params, jkey, xb, yb,
                                lr, w / total)
            acc = part if acc is None else jax.tree.map(jnp.add, acc, part)
        return jax.tree.map(lambda a, p: a.astype(p.dtype), acc, params)

    def train_fedavg_population(self, params_list, keys, client_ids, lr,
                                survivors=None):
        # gather the participants' train shards once for every individual
        with self.telemetry.span("stage"):
            batches = list(self._group_train_gather(client_ids, survivors))
        total = self._survivor_total(client_ids, survivors)
        if total == 0.0:               # nobody survived: models untouched
            return list(params_list)
        if self.cfg.fused:
            if not params_list:
                return []
            with self.telemetry.span("stage"):
                ps = jax.tree.map(lambda *xs: jnp.stack(xs), *params_list)
                karr = jnp.asarray(np.stack([np.asarray(k, np.int32)
                                             for k in keys]))
                buckets = tuple((xb, yb, jnp.asarray(w / total))
                                for xb, yb, w, _ in batches)
                lr = jnp.float32(lr)
            out = self._launch(self._fused_fedavg, ps, karr, buckets, lr)
            return [jax.tree.map(lambda x: x[i], out)
                    for i in range(len(params_list))]
        return [self._fedavg_from_batches(p, np.asarray(k, np.int32),
                                          batches, total, lr)
                for p, k in zip(params_list, keys)]

    def _eval_one(self, params, jkey, batches, masks, total):
        if total == 0:
            return 1.0                 # nobody evaluated: pessimistic
        wrong = 0
        for batch, alive in zip(batches, masks):
            m = batch.num_shards
            tile = max(1, min(self.cfg.vmap_eval_tile, m))
            full = (m // tile) * tile
            tail = batch.xb.shape[1:]
            if full:
                wrong += int(self._launch(
                    self._eval_tiles, params, jkey,
                    batch.xb[:full].reshape((full // tile, tile) + tail),
                    batch.yb[:full].reshape((full // tile, tile)
                                            + batch.yb.shape[1:]),
                    alive[:full].reshape((full // tile, tile))))
            if m > full:
                wrong += int(self._launch(self._eval_tiles, params, jkey,
                                          batch.xb[None, full:],
                                          batch.yb[None, full:],
                                          alive[None, full:]))
        return wrong / total

    def eval_shared(self, params, keys, client_ids, survivors=None):
        batches, masks, total, shards = self._eval_inputs(client_ids,
                                                          survivors)
        if self.cfg.fused:
            with self.telemetry.span("stage"):
                karr = jnp.asarray(np.stack([np.asarray(k, np.int32)
                                             for k in keys]))
            counts = self._launch(self._fused_eval_shared, params, karr,
                                  shards)
            return self._rates(counts, total, len(keys))
        return np.asarray([self._eval_one(params, np.asarray(k, np.int32),
                                          batches, masks, total)
                           for k in keys])

    def eval_paired(self, params_list, keys, client_ids, survivors=None):
        batches, masks, total, shards = self._eval_inputs(client_ids,
                                                          survivors)
        if self.cfg.fused:
            with self.telemetry.span("stage"):
                ps = jax.tree.map(lambda *xs: jnp.stack(xs), *params_list)
                karr = jnp.asarray(np.stack([np.asarray(k, np.int32)
                                             for k in keys]))
            counts = self._launch(self._fused_eval_paired, ps, karr, shards)
            return self._rates(counts, total, len(keys))
        return np.asarray([self._eval_one(p, np.asarray(k, np.int32),
                                          batches, masks, total)
                           for p, k in zip(params_list, keys)])



BACKENDS = {"loop": LoopBackend, "vmap": VmapBackend}
BACKEND_NAMES = ("loop", "mesh", "vmap")


def make_backend(name: str, api: SupernetAPI,
                 clients: Sequence[ClientDataset],
                 cfg: RunConfig) -> ExecutionBackend:
    """Build the execution backend ``name`` ('loop' | 'vmap' | 'mesh').

    Called by ``FedEngine.__init__`` — i.e. at configuration time, so an
    unknown name fails before any round runs.  ``MeshBackend`` lives in
    ``repro.engine.mesh_backend`` and registers itself into ``BACKENDS``
    when that module is imported (``repro.engine.__init__`` does so
    eagerly; no jax device/mesh state is touched until instantiation)."""
    try:
        cls = BACKENDS[name]
    except KeyError:
        raise ValueError(
            f"unknown execution backend {name!r}; "
            f"available: {list(BACKEND_NAMES)}") from None
    return cls(api, clients, cfg)
