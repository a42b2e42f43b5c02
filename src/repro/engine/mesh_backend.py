"""MeshBackend: device-mesh-sharded client execution for FedEngine.

The double-sampling design (paper Algorithm 4) makes every generation an
embarrassingly parallel population x client-group workload: group g
trains individual g's sub-model, and the 2N fitness evaluations are
independent.  ``VmapBackend`` already turns that structure into
O(population) jitted dispatches; this backend additionally shards the
*population axis* of the same ``ClientBatch``-stacked tensors over a
``jax.sharding.Mesh`` (``launch.mesh.make_host_mesh`` by default, any
mesh — e.g. ``make_production_mesh()`` — via the ``mesh=`` argument), so
a generation costs O(population / devices) dispatches and each device
only touches its slice of the population:

  * ``train_fill``   — (group, client)-stacked shards are gathered from
    the resident train store, padded to the mesh size, placed with
    ``NamedSharding`` (``launch.sharding.batch_spec``) and consumed by
    one ``shard_map`` program per shape bucket that fuses local SGD with
    the fill-aggregation partial sum (Algorithm 3); a ``psum`` over the
    population axes yields the replicated new master.
  * ``train_fedavg_population`` — individuals (stacked parameters +
    keys) are sharded over the mesh; every device FedAvg-trains its
    slice of the population on the (replicated) participant shards.
  * ``eval_shared`` / ``eval_paired`` — the 2N choice keys (and paired
    parameter stacks) are sharded; each device counts test errors for
    its keys over the replicated stacked test set, one dispatch per
    shape bucket for the WHOLE key batch.

With ``RunConfig.fused`` (the default) the per-bucket dispatches above
collapse to O(1) per generation: the shard_map programs are traceable,
so one jitted wrapper per phase loops the shape buckets *inside* the
dispatch — one ``train_fill`` program (master donated off-CPU when
``backends.master_donation_safe``) and one evaluation program whose
(2N,) wrong-count vector is fetched with a single ``jax.device_get``.
The program bodies themselves are shared with ``VmapBackend``
(``repro.engine.backends``: ``fill_bucket_partial``,
``eval_bucket_counts``, ...), which is what keeps reduction order — and
therefore parity — aligned across backends.  The
``aggregate_backend="pallas"`` route stays partially fused (sharded SGD
uploads per bucket, Algorithm 3 in the kernel outside the program).

Inside a shard every (individual, client) pair runs under ``lax.scan``
with the choice key a traced *scalar*, so ``lax.switch`` in the model
forward stays a real branch (vmapping the key axis would lower to
compute-all-branches-and-select — the 3-4x blowup documented on
``VmapBackend``).

Determinism / parity: padding rows carry weight 0 and weights are
normalized globally, so results match ``VmapBackend`` within fp32
reduction-order noise (<= 1e-5 on the smoke supernet; asserted by
``tests/test_engine.py``) and CommStats — which the strategies account,
independent of execution — match exactly.  Client dropout
(``ClientSimConfig``) rides the same weight-0 mechanism for training
and an int32 ``alive`` mask for the eval counts, so the sharded shapes
— and the O(1) fused dispatch count — are unchanged at any dropout
rate (see ``repro.engine.backends``).

Run multi-device on a plain CPU host with
``XLA_FLAGS=--xla_force_host_platform_device_count=8`` (set before jax
is imported; this is how CI exercises an 8-way mesh).

``RunConfig.aggregate_backend`` is honored like every other backend:
``"xla"`` uses the fused partial-sum path above; ``"pallas"`` returns
the sharded uploads and routes Algorithm 3 through the
``repro.kernels.fill_aggregate`` kernel via ``fill_aggregate_stacked``.

Payload codecs (``RunConfig.uplink_codec`` / ``downlink_codec``) are
likewise honored without touching the shard_map programs: ``FedEngine``
wraps this backend in ``repro.comm.backend.CodecBackend``, which
compresses the master each program consumes and the aggregated update
each ``train_fill`` produces — the fused SGD+Algorithm-3 psum path and
its reduction-order guarantees are codec-independent.
"""
from __future__ import annotations

from typing import Optional, Sequence

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import Mesh, NamedSharding, PartitionSpec

from repro.core.federated import client_update_fn, eval_count_fn
from repro.core.supernet import SupernetAPI
from repro.data.pipeline import ClientDataset
from repro.engine.backends import StackedClientBase, accumulate_parts, \
    cast_like, eval_bucket_counts, eval_paired_bucket_counts, \
    fedavg_population_bucket, fill_bucket_partial, master_donation_safe, \
    train_bucket_uploads
from repro.engine.types import RunConfig
from repro.launch.mesh import data_axes, make_host_mesh, mesh_axis_size
from repro.launch.sharding import batch_spec
from repro.obs import traced


class MeshBackend(StackedClientBase):
    """Population-axis-sharded execution over a jax device mesh.

    Args (beyond the ``ExecutionBackend`` constructor contract):
      * ``mesh`` — optional ``jax.sharding.Mesh``; defaults to
        ``launch.mesh.make_host_mesh()`` (all local devices on one
        ``data`` axis).  The population axis is sharded over
        ``launch.mesh.data_axes(mesh)``; the ``model`` axis is left for
        future tensor-parallel masters and must currently be size 1 in
        the axes this backend shards over.
    """

    name = "mesh"

    def __init__(self, api: SupernetAPI, clients: Sequence[ClientDataset],
                 cfg: RunConfig, mesh: Optional[Mesh] = None):
        super().__init__(api, clients, cfg)
        self.mesh = mesh if mesh is not None else make_host_mesh()
        self.axes = data_axes(self.mesh)
        self.num_devices = mesh_axis_size(self.mesh, self.axes)
        upd = client_update_fn(api, cfg.local_epochs, cfg.momentum)
        ev = eval_count_fn(api)
        mask_fn = api.trained_mask
        axes = self.axes
        pop = PartitionSpec(axes)       # leading axis sharded, rest replicated
        rep = PartitionSpec()
        self.donate_master = (cfg.fused and master_donation_safe(cfg)
                              and jax.default_backend() != "cpu")

        # The program bodies are the shared fused-bucket bodies from
        # repro.engine.backends — shard_map slices the population axis,
        # each device runs the identical body on its slice (so the vmap
        # backend's fp32 reduction order is preserved expression for
        # expression under sharding), and train adds a psum.

        # -- train_fill: fused local SGD + Algorithm 3 partial sum ----------
        def fill_body(master, keys, xb, yb, w, lr):
            # local shapes: keys (Gl, nb); xb/yb (Gl, S, nbat, B, ...);
            # w (Gl, S) globally normalized (0 = padding)
            return jax.lax.psum(
                fill_bucket_partial(upd, mask_fn, master,
                                    keys, xb, yb, w, lr), axes)

        fill_sm = jax.shard_map(
            fill_body, mesh=self.mesh,
            in_specs=(rep, pop, pop, pop, pop, rep),
            out_specs=rep, check_vma=False)
        # every jitted program is wrapped by repro.obs.traced (recompile
        # counter + named_scope label, exactly as in VmapBackend); the
        # fused wrappers below call the RAW shard_map callables, so each
        # trace bumps exactly one counter — no double counting
        tc = self.trace_counts
        self._fill_partial = jax.jit(traced("fill_partial", tc, fill_sm))

        # -- train_fill, kernel route: sharded SGD, uploads come back ------
        def uploads_body(master, keys, xb, yb, lr):
            return train_bucket_uploads(upd, master, keys, xb, yb, lr)

        self._train_uploads = jax.jit(traced(
            "train_uploads", tc, jax.shard_map(
                uploads_body, mesh=self.mesh,
                in_specs=(rep, pop, pop, pop, rep),
                out_specs=pop, check_vma=False)))

        # -- per-individual FedAvg over replicated participants -------------
        def fedavg_body(ps, keys, xb, yb, wn, lr):
            # ps leaves (Pl, ...), keys (Pl, nb) sharded;
            # xb/yb (S, nbat, B, ...) and wn (S,) replicated
            return fedavg_population_bucket(upd, ps, keys, xb, yb, wn, lr)

        fedavg_sm = jax.shard_map(
            fedavg_body, mesh=self.mesh,
            in_specs=(pop, pop, rep, rep, rep, rep),
            out_specs=pop, check_vma=False)
        self._fedavg_partial = jax.jit(traced("fedavg_partial", tc,
                                              fedavg_sm))

        # -- sharded-key evaluation over the replicated test stack ----------
        # (``alive`` is the replicated int32 survivor mask — dropped
        # clients' counts are zeroed inside the program, so the sharded
        # shapes stay static under any dropout rate)
        def eval_shared_body(params, keys, xb, yb, alive):
            return eval_bucket_counts(ev, params, keys, xb, yb, alive,
                                      tile=cfg.vmap_eval_tile)

        eval_shared_sm = jax.shard_map(
            eval_shared_body, mesh=self.mesh,
            in_specs=(rep, pop, rep, rep, rep),
            out_specs=pop, check_vma=False)
        self._eval_shared_counts = jax.jit(traced("eval_shared_counts", tc,
                                                  eval_shared_sm))

        def eval_paired_body(ps, keys, xb, yb, alive):
            return eval_paired_bucket_counts(ev, ps, keys, xb, yb, alive,
                                             tile=cfg.vmap_eval_tile)

        eval_paired_sm = jax.shard_map(
            eval_paired_body, mesh=self.mesh,
            in_specs=(pop, pop, rep, rep, rep),
            out_specs=pop, check_vma=False)
        self._eval_paired_counts = jax.jit(traced("eval_paired_counts", tc,
                                                  eval_paired_sm))

        # -- fused composition (cfg.fused): the shard_map programs above
        # are traceable, so one jitted wrapper per phase loops the shape
        # buckets INSIDE the dispatch — O(1) dispatches per generation,
        # and the master is donated off-CPU like the vmap backend.  The
        # combiners are the shared ones (accumulate_parts / cast_like),
        # only the per-bucket callable differs (shard_map-wrapped).
        def fused_fill(master, buckets, lr):
            return cast_like(accumulate_parts(
                fill_sm(master, keys, xb, yb, w, lr)
                for keys, xb, yb, w in buckets), master)

        def fused_eval_shared(params, keys, shards):
            return accumulate_parts(
                eval_shared_sm(params, keys, xb, yb, alive)
                for xb, yb, alive in shards)

        def fused_eval_paired(ps, keys, shards):
            return accumulate_parts(
                eval_paired_sm(ps, keys, xb, yb, alive)
                for xb, yb, alive in shards)

        def fused_fedavg(ps, keys, buckets, lr):
            return cast_like(accumulate_parts(
                fedavg_sm(ps, keys, xb, yb, wn, lr)
                for xb, yb, wn in buckets), ps)

        self._fused_fill = jax.jit(
            traced("fused_fill", tc, fused_fill),
            donate_argnums=(0,) if self.donate_master else ())
        self._fused_eval_shared = jax.jit(traced("fused_eval_shared", tc,
                                                 fused_eval_shared))
        self._fused_eval_paired = jax.jit(traced("fused_eval_paired", tc,
                                                 fused_eval_paired))
        self._fused_fedavg = jax.jit(traced("fused_fedavg", tc,
                                            fused_fedavg))

    # -- placement helpers --------------------------------------------------

    def _pad(self, n: int) -> int:
        """Rows to append so the leading axis divides the mesh."""
        return (-n) % self.num_devices

    def _put_pop(self, arr):
        """Place one stacked array with its leading (population) axis
        sharded over the mesh's data axes (``launch.sharding.batch_spec``)."""
        arr = jnp.asarray(arr)
        spec = batch_spec(self.mesh, arr.shape[0], arr.ndim)
        return jax.device_put(arr, NamedSharding(self.mesh, spec))

    def _put_pop_tree(self, tree):
        return jax.tree.map(self._put_pop, tree)

    def _put_rep(self, tree):
        """Replicate a pytree over the mesh (a no-op for arrays already
        placed so)."""
        return jax.device_put(tree, NamedSharding(self.mesh, PartitionSpec()))

    def _place_test(self, arr):
        """Replicate the cached test stacks over the mesh once, so the
        eval programs (in_specs=rep) never re-transfer them."""
        return self._put_rep(jnp.asarray(arr))

    # -- train_fill ----------------------------------------------------------

    def _group_bucket_arrays(self, keys, groups, total, pad_groups=None,
                             place=None, survivors=None, store=None):
        """The base builder with the group axis padded to a mesh multiple
        and every array placed population-sharded (weight-0 padding,
        which also carries the dropped-client survivor masking)."""
        g_pad = self._pad(len(groups)) if pad_groups is None else pad_groups
        return super()._group_bucket_arrays(
            keys, groups, total, pad_groups=g_pad,
            place=self._put_pop if place is None else place,
            survivors=survivors, store=store)

    def train_fill(self, master, keys, groups, lr, survivors=None):
        groups = [np.asarray(g) for g in groups]
        total = self._survivor_total([c for g in groups for c in g],
                                     survivors)
        if total == 0.0:
            return master
        with self.telemetry.span("stage"):
            buckets = self._group_bucket_arrays(keys, groups, total,
                                                survivors=survivors)
            if buckets:
                # the fill programs return a mesh-replicated master;
                # placing the incoming one the same way gives them one
                # input sharding from the first generation on, so each
                # traces once
                master = self._put_rep(master)
            lr = jnp.float32(lr)
        if not buckets:
            return master
        if self.cfg.aggregate_backend == "pallas":
            return self._train_fill_pallas(master, buckets, lr)
        if self.cfg.fused:
            # one dispatch for the whole generation's fill-train (the
            # bucket loop runs inside the program; donated master)
            return self._launch(self._fused_fill, master, tuple(buckets), lr)
        acc = None
        for keys_a, xb, yb, w in buckets:
            part = self._launch(self._fill_partial, master, keys_a, xb, yb,
                                w, lr)
            acc = part if acc is None else jax.tree.map(jnp.add, acc, part)
        return jax.tree.map(lambda a, p: a.astype(p.dtype), acc, master)

    def _train_fill_pallas(self, master, buckets, lr):
        """Kernel route: run the sharded local SGD per bucket and hand the
        uploads to the shared ``_pallas_fill`` (weight-0 padding groups
        are skipped, padding rows contribute nothing)."""
        outs = [self._launch(self._train_uploads, master, keys_a, xb, yb, lr)
                for keys_a, xb, yb, _ in buckets]
        return self._pallas_fill(master, buckets, outs)

    # -- FedAvg paths (train_fedavg delegates via StackedClientBase) ---------

    def train_fedavg_population(self, params_list, keys, client_ids, lr,
                                survivors=None):
        if not params_list:
            return []
        total = self._survivor_total(client_ids, survivors)
        if total == 0.0:               # nobody survived: models untouched
            return list(params_list)
        n = len(params_list)
        pad = self._pad(n)
        with self.telemetry.span("stage"):
            plist = list(params_list) + [params_list[-1]] * pad
            klist = [np.asarray(k, np.int32) for k in keys]
            klist = klist + [klist[-1]] * pad
            stacked = self._put_pop_tree(
                jax.tree.map(lambda *xs: jnp.stack(xs), *plist))
            keys_arr = self._put_pop(np.stack(klist))
            lr = jnp.float32(lr)
            buckets = tuple((xb, yb, jnp.asarray(w / total))
                            for xb, yb, w, _ in
                            self._group_train_gather(client_ids, survivors))
        if self.cfg.fused:
            out = self._launch(self._fused_fedavg, stacked, keys_arr,
                               buckets, lr)
            return [jax.tree.map(lambda x: x[i], out) for i in range(n)]
        acc = None
        for xb, yb, wn in buckets:
            part = self._launch(self._fedavg_partial, stacked, keys_arr, xb,
                                yb, wn, lr)
            acc = part if acc is None else jax.tree.map(jnp.add, acc, part)
        out = jax.tree.map(lambda a, s: a.astype(s.dtype), acc, stacked)
        return [jax.tree.map(lambda x: x[i], out) for i in range(n)]

    # -- evaluation ----------------------------------------------------------

    def _padded_keys(self, keys):
        klist = [np.asarray(k, np.int32) for k in keys]
        klist = klist + [klist[-1]] * self._pad(len(klist))
        return self._put_pop(np.stack(klist))

    def _eval_counts(self, fused_program, bucket_program, params, karr,
                     shards, total, n_keys):
        """Error rates from ``fused_program`` over every test stack
        (``cfg.fused``), or from one ``bucket_program`` dispatch per
        stack summed on the host."""
        if self.cfg.fused:
            counts = self._launch(fused_program, params, karr, shards)
            return self._rates(counts, total, n_keys)
        wrong = np.zeros(karr.shape[0], np.int64)
        for xb, yb, m in shards:
            counts = self._launch(bucket_program, params, karr,
                                  jnp.asarray(xb), jnp.asarray(yb),
                                  jnp.asarray(m))
            wrong += np.asarray(counts, np.int64)
        return wrong[:n_keys] / total

    def eval_shared(self, params, keys, client_ids, survivors=None):
        _, _, total, shards = self._eval_inputs(client_ids, survivors)
        if total == 0:                 # nobody evaluated: pessimistic
            return np.ones(len(keys))
        with self.telemetry.span("stage"):
            karr = self._padded_keys(keys)
        return self._eval_counts(self._fused_eval_shared,
                                 self._eval_shared_counts, params, karr,
                                 shards, total, len(keys))

    def eval_paired(self, params_list, keys, client_ids, survivors=None):
        _, _, total, shards = self._eval_inputs(client_ids, survivors)
        if total == 0:                 # nobody evaluated: pessimistic
            return np.ones(len(keys))
        with self.telemetry.span("stage"):
            pad = self._pad(len(params_list))
            plist = list(params_list) + [params_list[-1]] * pad
            stacked = self._put_pop_tree(
                jax.tree.map(lambda *xs: jnp.stack(xs), *plist))
            karr = self._padded_keys(keys)
        return self._eval_counts(self._fused_eval_paired,
                                 self._eval_paired_counts, stacked, karr,
                                 shards, total, len(keys))


from repro.engine import backends as _backends  # noqa: E402

_backends.BACKENDS.setdefault("mesh", MeshBackend)
