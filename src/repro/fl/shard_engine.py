"""Client-sharded scanned engine: ``shard_map`` over the mesh "data" axis.

The scanned engine (:mod:`repro.fl.scan_engine`) made a full FL run one
XLA program, but the whole client axis lives on one chip — client count
K is capped by a single device's memory.  This engine partitions the
client axis across the mesh defined in :mod:`repro.launch.mesh`: the
scan body runs under ``shard_map`` with every per-client tensor (stacked
params, private shards, eval shards, ``last_sync``) split over the
"data" axis, so each shard trains and predicts only its ``K / n_shards``
clients.

What crosses shards is exactly the strategy's *linear* aggregation
moments plus a handful of scalar reductions:

- aggregation uses the two-phase ``Strategy.partial_aggregate`` /
  ``finalize_aggregate`` contract — per-shard weighted sums, one
  ``psum``, then the nonlinearity (Enhanced-ERA sharpening, DS-FL
  temperature softmax, Selective-FD gating ratio) applied once on the
  replicated reduction;
- byte accounting threads shard-local counts through the shard-aware
  cost functions (``comm.distillation_round_cost_device(axis_name=...)``
  psums the per-shard participant count; catch-up bytes are computed
  from the replicated ``last_sync``/participation state — the identical
  expression the scanned engine evaluates);
- eval metrics psum per-shard (per-cohort) partial sums.

Client-model cohorts (:mod:`repro.fl.cohorts`) shard naturally: every
cohort's contiguous client block is partitioned independently over the
same "data" axis (cohort sizes must divide the shard count), so each
shard holds an equal per-cohort composition and the SPMD program stays
uniform.  Soft-labels collapse the cohort axis before aggregation, so
the two-phase Strategy contract and the psum'd cost functions are
untouched by the mix.

Everything server-side (cache state, teacher assembly, server
distillation, the public dataset) is replicated — redundantly computed
by every shard, which keeps it bit-identical across shards without
communication.

Parity contract: participation and subset sampling fold the *same* key
stream as the scanned engine, with the participation mask drawn over
the full client axis on every shard (replicated — conscription ranks
couple clients across shards) and then sliced locally.  All ledger
inputs are therefore exact small-integer sums, so a sharded run's
per-round comm ledger is byte-identical to ``engine="scan"`` and eval
metrics are allclose (float reduction order differs) — asserted for the
whole strategy x participation x codec matrix by
``tests/test_engine_conformance.py``.
"""
from __future__ import annotations

import math
import re
from typing import Optional, Union

import jax
import jax.numpy as jnp
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from repro.core import cache as cache_lib
from repro.core import comm as comm_lib
from repro.obs import device as obs_device
from repro.obs.trace import stage
from repro.fl.rounds import (
    _select_cohorts,
    accuracy,
    accuracy_v,
    distill,
    distill_v,
    local_train_masked_v,
    local_train_v,
    val_loss_hard_v,
    val_loss_soft,
)
from repro.fl.scan_engine import (
    ScannedFederatedDistillation,
    compiler_options,
)
from repro.fl.strategies.base import TRANSMIT_SALT
from repro.kernels import round_kernel
from repro.launch.mesh import (
    make_production_mesh,
    make_test_mesh,
    mesh_axis_sizes,
)

__all__ = ["ShardedFederatedDistillation", "resolve_mesh", "best_data_axis"]

# The mesh axis carrying the client partition — the same "data" axis the
# launch-layer sharding rules use for data parallelism / FSDP.
CLIENT_AXIS = "data"

_SPEC_RE = re.compile(r"^(\d+)(?:x(\d+))?$")


def resolve_mesh(spec: Union[str, Mesh]) -> Mesh:
    """Mesh from a *concrete* ``FLConfig.mesh_spec`` (or a Mesh, as-is).

    ``"DATA"`` or ``"DATAxMODEL"`` (e.g. ``"8"``, ``"2x4"``): a
    :func:`repro.launch.mesh.make_test_mesh` of that shape.
    ``"production"`` / ``"production_multipod"``: the 16x16 (2x16x16)
    pod meshes.

    ``"auto"`` is resolved *before* this function by the engine
    constructor (via :func:`best_data_axis`, which needs the client
    count) and is rejected here so the spelling has exactly one meaning.
    """
    if isinstance(spec, Mesh):
        return spec
    if spec == "production":
        return make_production_mesh()
    if spec == "production_multipod":
        return make_production_mesh(multi_pod=True)
    m = _SPEC_RE.match(spec) if isinstance(spec, str) else None
    if m is None:
        raise ValueError(
            f"unknown mesh_spec {spec!r} (want 'DATA', 'DATAxMODEL', "
            "'production', or 'production_multipod'; 'auto' is only valid "
            "through the engine constructor / FLConfig.mesh_spec)")
    return make_test_mesh(int(m.group(1)), int(m.group(2) or 1))


def best_data_axis(n_clients: int, n_devices: Optional[int] = None) -> int:
    """Largest device count <= ``n_devices`` that divides ``n_clients``
    evenly — the widest legal client partition for a run (benchmarks use
    it to build meshes portable across device counts)."""
    d = min(n_clients, n_devices if n_devices is not None else jax.device_count())
    while n_clients % d:
        d -= 1
    return d


class ShardedFederatedDistillation(ScannedFederatedDistillation):
    """Client-sharded twin of :class:`ScannedFederatedDistillation`.

    Same constructor plus ``mesh``: a concrete :class:`Mesh`, a spec
    string (see :func:`resolve_mesh`), or ``None`` to use
    ``cfg.mesh_spec``.  ``cfg.n_clients`` must divide evenly by the
    mesh's "data"-axis size.  Every mode restriction of the scanned
    engine applies unchanged (jax RNG, scan-safe strategy/codecs, no
    ``track_local_caches``).
    """

    def __init__(self, *args, mesh: Union[str, Mesh, None] = None, **kwargs):
        super().__init__(*args, **kwargs)
        spec = mesh if mesh is not None else self.cfg.mesh_spec
        if spec is None or spec in ("", "auto"):
            # widest client partition over the local devices that splits
            # every cohort block evenly (gcd of the cohort sizes; the
            # whole K for a homogeneous run) — "auto" must never reject
            # a client count or a cohort mix
            spec = f"{best_data_axis(math.gcd(*self.models.sizes))}"
        self.mesh = resolve_mesh(spec)
        if CLIENT_AXIS not in self.mesh.axis_names:
            raise ValueError(
                f"mesh {self.mesh.axis_names} has no {CLIENT_AXIS!r} axis "
                "to partition clients over")
        self.n_shards = mesh_axis_sizes(self.mesh)[CLIENT_AXIS]
        if self.cfg.n_clients % self.n_shards:
            raise ValueError(
                f"n_clients={self.cfg.n_clients} does not divide evenly over "
                f"the {self.n_shards}-way {CLIENT_AXIS!r} axis "
                "(pick a divisible client count or a narrower mesh)")
        # every cohort's block is sharded independently, so each cohort
        # size must split evenly too (equal per-cohort composition on
        # every shard keeps the SPMD program uniform)
        self.kloc_c = self.models.shard_sizes(self.n_shards)
        self._shard_fn = None

    # ------------------------------------------------------------------
    def _consts(self) -> dict:
        """Arrays the round body reads besides the carry: client-sharded
        private/eval shards (per-cohort tuples — each cohort's block is
        partitioned independently over the client axis) and replicated
        public/test data."""
        consts = dict(
            xs=tuple(self.xs_c), ys=tuple(self.ys_c),
            train_mask=tuple(self.train_mask_c),
            xts=tuple(self.xts_c), yts=tuple(self.yts_c),
            tmask=tuple(self.tmask_c), val_mask=tuple(self.val_mask_c),
            x_pub=self.x_pub, x_test=self.x_test, y_test=self.y_test,
            x_pub_val=self.x_pub[self.pub_val_idx],
        )
        if self.scenario.heterogeneity is not None:
            consts.update(lr_k=tuple(self._lr_k_c),
                          steps_k=tuple(self._steps_k_c))
        return consts

    def _specs(self):
        """(carry, xs, consts) PartitionSpec pytrees (prefix form)."""
        cax, rep = P(CLIENT_AXIS), P()
        # last_sync stays REPLICATED: its update depends only on the
        # (replicated) global participation draw, so keeping it global
        # avoids axis_index-tainted dataflow in an int carry — which the
        # SPMD partitioner (check_vma=False) cannot prove replicated
        # over non-client mesh axes and would mis-reduce on the gather.
        carry = dict(
            client_params=cax, server_params=rep, cache=rep,
            prev_teacher=rep, prev_idx=rep, have_prev=rep,
            teacher_val=rep, have_tv=rep, last_sync=rep)
        consts = dict(
            xs=cax, ys=cax, train_mask=cax, xts=cax, yts=cax, tmask=cax,
            val_mask=cax, x_pub=rep, x_test=rep, y_test=rep, x_pub_val=rep)
        if self.scenario.heterogeneity is not None:
            consts.update(lr_k=cax, steps_k=cax)
        # xs = (ts, offline, do_eval): offline stays full-width (T, K) on
        # every shard — the participation draw is global (see body)
        return carry, (rep, rep, rep), consts

    # ------------------------------------------------------------------
    def _local_train_shard(self, params, t, consts):
        c = self.cfg
        if self.scenario.heterogeneity is None:
            return [local_train_v(p, consts["xs"][i], consts["ys"][i],
                                  consts["train_mask"][i].astype(jnp.float32),
                                  c.lr, c.local_steps)
                    for i, p in enumerate(params)]
        decay = jnp.asarray(self._lr_decay, jnp.float32) ** (
            jnp.asarray(t, jnp.float32) - 1.0)
        return [local_train_masked_v(p, consts["xs"][i], consts["ys"][i],
                                     consts["train_mask"][i].astype(jnp.float32),
                                     consts["lr_k"][i] * decay,
                                     consts["steps_k"][i], self._max_steps)
                for i, p in enumerate(params)]

    # ------------------------------------------------------------------
    def _round_device_sharded(self, carry, xs, consts):
        """One round on one shard: mirrors ``_round_device`` with the
        client axis shard-local and all cross-client couplings reduced
        via ``psum`` over the client mesh axis."""
        c, s = self.cfg, self.strategy
        K = c.n_clients
        t, offline_t, do_eval = xs

        with stage("cache"):
            kt = jax.random.fold_in(self._key_rounds, t)
            k_idx, k_part = jax.random.split(kt)
            idx = jnp.sort(jax.random.choice(
                k_idx, c.public_size, (c.public_per_round,), replace=False))
            # Participation is drawn over the FULL client axis on every
            # shard (replicated: same key -> same draw) — conscription
            # ranks couple clients across shards and key-stream parity
            # with engine="scan" requires the identical global sample —
            # then sliced locally, one block per cohort (cohort c's
            # shard-s clients are the global indices
            # offset_c + s*kloc_c .. offset_c + (s+1)*kloc_c).
            part_full = self.scenario.participation_mask_device(k_part,
                                                                offline_t)
            six = jax.lax.axis_index(CLIENT_AXIS)
            part_c = [jax.lax.dynamic_slice_in_dim(part_full, off + six * kc,
                                                   kc)
                      for off, kc in zip(self.models.offsets, self.kloc_c)]
            part = self.models.concat(part_c)          # shard-local (kloc,)
            part_f = part.astype(jnp.float32)
            # global, replicated
            n_part = jnp.sum(part_full.astype(jnp.float32))
            any_p = n_part > 0

        def gate(new, old):
            """Keep ``old`` wholesale on total-outage rounds."""
            return jax.tree_util.tree_map(
                lambda a, b: jnp.where(any_p, a, b), new, old)

        # --- clients (shard-local, per cohort): distill, then train ------
        cp = carry["client_params"]
        with stage("client_distill"):
            x_prev = consts["x_pub"][carry["prev_idx"]]
            teacher_prev = carry["prev_teacher"]
            upd = [distill_v(p, x_prev,
                             jnp.broadcast_to(teacher_prev,
                                              (kc,) + teacher_prev.shape),
                             c.lr_dist, c.distill_steps)
                   for p, kc in zip(cp, self.kloc_c)]
            cp = _select_cohorts(
                upd, cp, [jnp.logical_and(pc, carry["have_prev"])
                          for pc in part_c])
        with stage("local_train"):
            upd = self._local_train_shard(cp, t, consts)
            cp = _select_cohorts(upd, cp, part_c)

        # --- request list (replicated cache) -----------------------------
        cache_prev = carry["cache"]
        with stage("cache"):
            if self.use_cache:
                key_exp = (jax.random.fold_in(jax.random.PRNGKey(c.seed), t)
                           if self.probabilistic_expiry else None)
                miss = cache_lib.miss_mask(
                    cache_prev, idx, t, self.D,
                    probabilistic=self.probabilistic_expiry, key=key_exp)
            else:
                miss = jnp.ones(c.public_per_round, bool)
            miss_f = miss.astype(jnp.float32)
            n_req = jnp.sum(miss_f)
            base, base_present = cache_lib.cached_at(cache_prev, idx)

        # --- uplink + two-phase aggregation ------------------------------
        # the cohort axis collapses here: soft-label shapes are
        # architecture-independent, so codec/strategy/ledger code below
        # is identical to the homogeneous path
        with stage("predict"):
            x_round = consts["x_pub"][idx]
            z_all = self._predict_all(cp, x_round)         # (kloc, m, N)
        with stage("aggregate"):
            # per-round transmit key, replicated across shards (same fold
            # on every shard; DCE'd when the strategy ignores it)
            z_all = s.transmit(z_all, jax.random.fold_in(kt, TRANSMIT_SALT))
            z_tx = z_all  # as transmitted: telemetry's codec-error reference
            if self._fused:
                # fused fast path: codec round trip + linear moments in
                # one round_kernel pass per shard; the psum + finalize
                # nonlinearity are unchanged from the per-op two-phase path
                um = s.upload_mask(z_all)
                fbase = (round_kernel.resolve_delta_base(
                             base, base_present, c.public_per_round,
                             c.n_classes)
                         if self._fused_spec["mode"] == "delta" else None)
                partials = jax.lax.psum(
                    s.partial_aggregate_fused(z_all, part_f,
                                              self._fused_spec, fbase, t),
                    CLIENT_AXIS)
            else:
                if not self.codec_up.is_identity:
                    z_all = self.codec_up.roundtrip(z_all, base=base,
                                                    present=base_present)
                um = s.upload_mask(z_all)
                partials = jax.lax.psum(
                    s.partial_aggregate(z_all, part_f, um, t), CLIENT_AXIS)
            fresh = s.finalize_aggregate(partials, t)      # replicated
            if not self.codec_down.is_identity:
                fresh = self.codec_down.roundtrip(fresh, base=base,
                                                  present=base_present)

        # --- teacher + cache + server distill (replicated) ---------------
        with stage("cache"):
            cache = cache_prev
            if self.use_cache:
                teacher = cache_lib.assemble_teacher(cache_prev, idx, fresh,
                                                     miss)
                new_cache, _ = cache_lib.update_global_cache(
                    cache_prev, idx, teacher, miss, t)
                cache = gate(new_cache, cache_prev)
            else:
                teacher = fresh
        with stage("server_distill"):
            sp = distill(carry["server_params"], x_round, teacher,
                         c.lr_dist, c.distill_steps)
            server_params = gate(sp, carry["server_params"])

        with stage("predict"):
            # (kloc, n_val, N)
            zv = self._predict_all(cp, consts["x_pub_val"])
            zv_sum = jax.lax.psum(jnp.sum(zv, axis=0), CLIENT_AXIS)
            teacher_val = jnp.where(any_p, zv_sum / K, carry["teacher_val"])
            have_tv = jnp.logical_or(carry["have_tv"], any_p)

        with stage("cache"):
            prev_teacher = jnp.where(any_p, teacher, carry["prev_teacher"])
            prev_idx = jnp.where(any_p, idx, carry["prev_idx"])
            have_prev = jnp.logical_or(carry["have_prev"], any_p)

            # --- byte accounting ----------------------------------------
            # last_sync and the participation draw are both replicated,
            # so catch-up bytes are computed globally on every shard —
            # the *identical* expression the scanned engine evaluates,
            # hence byte-equal ledgers by construction (no psum needed)
            catch_up = 0.0
            if self.use_cache:
                catch_up = cache_lib.catch_up_bytes_device(
                    cache_prev, carry["last_sync"], part_full, t)
            n_up = n_req
            if um is not None:  # Selective-FD: psum the uploaded entries
                uploaded_total = jax.lax.psum(jnp.sum(
                    um.astype(jnp.float32) * part_f[:, None]
                    * miss_f[None, :]), CLIENT_AXIS)
                n_up = uploaded_total / jnp.maximum(n_part, 1.0)
            uplink, downlink = comm_lib.distillation_round_cost_device(
                n_clients=jnp.sum(part_f),  # per-shard count; psum'd inside
                n_selected=float(c.public_per_round),
                n_up_samples=n_up,
                n_down_samples=n_req,
                n_classes=c.n_classes,
                uplink_bits=s.uplink_bits,
                downlink_bits=s.downlink_bits,
                with_cache_signals=self.use_cache,
                catch_up_down=catch_up,
                bytes_index=c.index_bytes,
                uplink_codec=self.codec_up,
                downlink_codec=self.codec_down,
                axis_name=CLIENT_AXIS,
            )
            uplink = jnp.where(any_p, uplink, 0.0)
            downlink = jnp.where(any_p, downlink, 0.0)
            last_sync = jnp.where(part_full, t, carry["last_sync"])

        # --- device-plane telemetry: counters from the replicated
        # full-width draw/last_sync, gauges from the shard-local stack
        # psum'd over the client axis inside _telemetry_row ----------------
        tel = None
        if self._telemetry:
            z_srv = z_all
            if self._fused and not self.codec_up.is_identity:
                z_srv = self.codec_up.roundtrip(z_tx, base=base,
                                                present=base_present)
            tel = obs_device.gate(self._telemetry_row(
                t=t, part_full=part_full, miss=miss,
                base_present=base_present, z_tx=z_tx, z_srv=z_srv,
                fresh=fresh, last_sync=carry["last_sync"], uplink=uplink,
                downlink=downlink, catch_up=catch_up,
                axis_name=CLIENT_AXIS, part_local=part_f), any_p)

        # --- eval: shard-local per-cohort partial sums under the cond,
        # psum outside (collectives stay unconditional; do_eval is
        # replicated) -----------------------------------------------------
        def _eval_local():
            sa = accuracy(server_params, consts["x_test"], consts["y_test"],
                          jnp.ones(consts["y_test"].shape[0]))
            acc_sums = jnp.stack([jnp.sum(accuracy_v(
                p, consts["xts"][i], consts["yts"][i],
                consts["tmask"][i].astype(jnp.float32)))
                for i, p in enumerate(cp)])            # (n_cohorts,)
            sv = val_loss_soft(server_params, consts["x_pub_val"], teacher_val)
            cv_part = sum(jnp.sum(val_loss_hard_v(
                p, consts["xs"][i], consts["ys"][i],
                consts["val_mask"][i].astype(jnp.float32)))
                for i, p in enumerate(cp))
            return sa, acc_sums, sv, cv_part

        with stage("eval"):
            sa, acc_sums, sv, cv_part = jax.lax.cond(
                do_eval, _eval_local,
                lambda: (jnp.float32(0),
                         jnp.zeros(self.models.n_cohorts, jnp.float32),
                         jnp.float32(0), jnp.float32(0)))
            acc_sums = jax.lax.psum(acc_sums, CLIENT_AXIS)  # global per cohort
            cacc = acc_sums / jnp.asarray(self.models.sizes, jnp.float32)
            ca = jnp.sum(acc_sums) / K
            cv = jax.lax.psum(cv_part, CLIENT_AXIS) / K

        new_carry = dict(
            client_params=cp,
            server_params=server_params,
            cache=cache,
            prev_teacher=prev_teacher,
            prev_idx=prev_idx,
            have_prev=have_prev,
            teacher_val=teacher_val,
            have_tv=have_tv,
            last_sync=last_sync,
        )
        ys = dict(uplink=uplink, downlink=downlink,
                  server_acc=sa, client_acc=ca, server_val=sv, client_val=cv,
                  cohort_acc=cacc, have_tv=have_tv)
        if tel is not None:
            ys["telemetry"] = tel
        return new_carry, ys

    # ------------------------------------------------------------------
    def _program(self):
        if self._shard_fn is None:
            carry_specs, xs_specs, consts_specs = self._specs()
            in_specs = (carry_specs, xs_specs, consts_specs)

            def scan_all(carry, xs, consts):
                return jax.lax.scan(
                    lambda cr, x: self._round_device_sharded(cr, x, consts),
                    carry, xs)

            # pin input shardings so chained run() calls hit one compile:
            # the first call feeds host/single-device arrays, later calls
            # feed the previous run's already-sharded outputs
            shardings = jax.tree_util.tree_map(
                lambda s: NamedSharding(self.mesh, s), in_specs,
                is_leaf=lambda x: isinstance(x, P))
            self._shard_fn = jax.jit(
                jax.shard_map(scan_all, mesh=self.mesh, in_specs=in_specs,
                              out_specs=(carry_specs, P()),
                              check_vma=False),
                in_shardings=shardings,
                compiler_options=compiler_options())
        return self._shard_fn

    def _aot_args(self, ts, offline, do_eval):
        return (self._initial_carry(), (ts, offline, do_eval),
                self._consts())

    # ------------------------------------------------------------------
    def carry_update_fn(self):
        """The one-round carry update under the engine's real shard_map,
        plus matching abstract arguments — the entry point for the
        static replication checker
        (:mod:`repro.analysis.replication_checks`).

        The round program runs with ``check_vma=False`` (the scan body
        defeats the partitioner's replication inference), so nothing at
        compile time verifies that the carry leaves ``_specs()``
        declares replicated (``P()``) really stay bit-identical across
        client shards — the exact invariant the PR 5 ``last_sync`` bug
        violated.  The checker traces ``jax.make_jaxpr(fn)(*abstract)``
        (one shard_map equation) and proves it by ``axis_index`` taint
        analysis instead.
        """
        carry_specs, xs_specs, consts_specs = self._specs()
        fn = jax.shard_map(
            lambda carry, xs, consts: self._round_device_sharded(
                carry, xs, consts),
            mesh=self.mesh, in_specs=(carry_specs, xs_specs, consts_specs),
            out_specs=(carry_specs, P()), check_vma=False)
        abstract = jax.tree_util.tree_map(
            lambda x: jax.ShapeDtypeStruct(jnp.shape(x), jnp.result_type(x)),
            (self._initial_carry(),
             (jnp.int32(0), jnp.zeros(self.cfg.n_clients, bool),
              jnp.asarray(False)),
             self._consts()))
        return fn, abstract
