"""Active-set engine: million-client populations, O(m) device compute.

SCARLET's evaluation (like DS-FL's) samples a small fraction of clients
per round, yet every dense engine materializes a K-stacked parameter
pytree on device, so the population is bounded by accelerator memory —
exactly the gap between simulation scale and production-scale federated
distillation, where per-round cost is driven by the m participants, not
the population (Sattler et al. 2020).  This engine removes that bound:

- **client state lives on the host** in a
  :class:`repro.checkpoint.ClientParamStore` (plain numpy or
  memory-mapped files; optionally persisted in row-sharded npz files) —
  per-client data shards, masks, and schedules stay host-side numpy via
  the ``rounds.py`` placement hooks;
- **each round draws participation over the full K** from the *exact*
  device key stream the dense engines fold
  (``fold_in(key_rounds, t)`` -> ``split`` -> subset choice /
  ``scenarios.participation_mask_device``), so the participation and
  request-list draws — and therefore the comm ledger — match the dense
  engines byte-for-byte;
- **only the m active clients are gathered** into a device stack
  (padded to the next power of two so jit signatures stay few), the
  scan-engine round body runs on that stack, and updated rows scatter
  back to the store;
- **O(K)-but-tiny bookkeeping stays on device**: ``last_sync``,
  participation counters, and catch-up byte accounting run as one small
  jitted step over ``(K,)`` integer arrays
  (``cache.catch_up_bytes_device(method="sorted")`` — the O(K + |P|)
  counting kernel that never materializes the dense engines' (K, |P|)
  comparison matrix), which is what keeps the ledger exact at K = 10^6.

Parity contract (``tests/test_engine_conformance.py``): every ledger
input is an exact small-integer count (participants, misses, catch-up
entry counts), evaluated by the same
``comm.distillation_round_cost_device`` expression the scan engine
traces — so active ledgers are **byte-identical** to scan/shard and
float32-exact against the host loop.  Metrics and cache values agree to
float reduction order (the gathered stack sums m rows where the dense
engines sum K mostly-masked rows).  One documented exception:
Selective-FD's fractional per-client upload average is a float
reduction over the stack, so its ledger is allclose, not byte-equal —
the same caveat the scan engine's ``um`` path already carries.

Restore-then-continue is bit-identical (``tests/test_checkpoint.py``):
``state_dict()`` reassembles the dense ``client_params`` structure from
the store, rounds are numbered absolutely, and the key stream is keyed
by absolute round.

``repro.analysis.active_checks`` proves the split at trace time: the
gathered client step's jaxpr must contain **no K-sized array** (the
O(K) bookkeeping may never leak into the O(m) compute), and both jitted
steps must be scan-safe (no host callbacks / host RNG).
"""
from __future__ import annotations

from typing import Any, Dict, List, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from repro.checkpoint.store import ClientParamStore
from repro.core import cache as cache_lib
from repro.core import comm as comm_lib
from repro.kernels import round_kernel
from repro.obs import device as obs_device
from repro.fl.scan_engine import (
    ScannedFederatedDistillation,
    compiler_options,
)
from repro.fl.rounds import (
    FederatedDistillation,
    History,
    accuracy,
    accuracy_v,
    distill,
    distill_v,
    local_train_masked_v,
    local_train_v,
    predict_v,
    val_loss_hard_v,
    val_loss_soft,
)
from repro.fl.strategies.base import TRANSMIT_SALT

__all__ = ["ActiveSetFederatedDistillation"]


def _next_pow2(n: int) -> int:
    return 1 << max(n - 1, 0).bit_length() if n > 1 else 1


class ActiveSetFederatedDistillation(ScannedFederatedDistillation):
    """Active-set twin of the scan engine: host-resident client store,
    O(m) gathered device compute, byte-exact O(K) ledger bookkeeping.

    Same constructor as the dense engines plus the store knobs:
    ``store_backing`` (``"ram"`` | ``"memmap"``), ``store_dir`` (backing
    directory, required for memmap), ``init_chunk`` (clients initialised
    per device call), ``eval_chunk`` (clients evaluated per device call
    on eval rounds — eval is the one remaining O(K) *compute* pass, run
    chunked on the ``eval_every`` schedule only).
    """

    def __init__(self, *args, store_backing: str = "ram",
                 store_dir: Optional[str] = None, init_chunk: int = 65536,
                 eval_chunk: int = 4096, **kwargs):
        self._store_backing = store_backing
        self._store_dir = store_dir
        self._init_chunk = init_chunk
        self._eval_chunk = eval_chunk
        self._last_sync_dev = None
        super().__init__(*args, **kwargs)
        opts = compiler_options()
        self._client_step_jit = jax.jit(self._client_step,
                                        compiler_options=opts)
        self._bookkeeping_jit = jax.jit(self._bookkeeping_step,
                                        compiler_options=opts)

    # ------------------------------------------------------------------
    # Placement hooks (rounds.py): per-client state stays host numpy.
    # ------------------------------------------------------------------
    def _client_array(self, x):
        return np.asarray(x)

    def _eval_array(self, x):
        return np.asarray(x)

    def _init_client_params(self, keys) -> None:
        self._store = ClientParamStore(
            self.models, keys, backing=self._store_backing,
            directory=self._store_dir, init_chunk=self._init_chunk)

    # client_params stays the dense engines' list-of-stacked-pytrees
    # view (numpy leaves), reassembled from / ingested into the store —
    # the shared state_dict()/load_state_dict() plumbing works unchanged.
    @property
    def client_params(self) -> List[Any]:
        return self._store.as_param_list()

    @client_params.setter
    def client_params(self, value) -> None:
        self._store.ingest_param_list(value)

    @property
    def store(self) -> ClientParamStore:
        return self._store

    # ------------------------------------------------------------------
    def run(self, rounds: Optional[int] = None) -> History:
        # host round loop (the store gather/scatter is inherently
        # host-paced); each round launches the two jitted steps below
        return FederatedDistillation.run(self, rounds)

    # ------------------------------------------------------------------
    def _get_last_sync_dev(self) -> jnp.ndarray:
        if self._last_sync_dev is None:
            self._last_sync_dev = jnp.asarray(self.last_sync, jnp.int32)
        return self._last_sync_dev

    # ------------------------------------------------------------------
    # O(K) bookkeeping step: tiny integer arrays, one jitted program.
    # ------------------------------------------------------------------
    def _bookkeeping_step(self, cache_prev, last_sync, part, t) -> Dict:
        catch_up = jnp.float32(0.0)
        if self.use_cache:
            # sorted counting kernel: same integer counts (and therefore
            # the same f32 total) as the dense engines' (K, |P|) matrix,
            # in O(K + |P| log |P|) memory
            catch_up = cache_lib.catch_up_bytes_device(
                cache_prev, last_sync, part, t, method="sorted")
        out = dict(catch_up=catch_up,
                   last_sync=jnp.where(part, t, last_sync))
        if self._telemetry:
            out["participants"] = obs_device.participants_per_cohort(
                part, self.models.offsets, self.models.sizes)
            out["catch_up_clients"] = obs_device.returning_client_count(
                part, last_sync, t)
            out["staleness_hist"] = obs_device.staleness_histogram(
                part, last_sync, t)
        return out

    # ------------------------------------------------------------------
    # Gather plan: per cohort, the active row indices (ascending, so the
    # concatenated cohort-major stack is in global client order — the
    # same participant order the dense engines' z_all[part] would see),
    # padded to the next power of two with duplicates of the first
    # active row.  Padding rows carry validity False and therefore
    # exactly-zero aggregation weight; they train redundantly and are
    # dropped at scatter.
    # ------------------------------------------------------------------
    def _gather_plan(self, part: np.ndarray) -> List[Tuple[int, np.ndarray,
                                                           np.ndarray]]:
        plan = []
        for ci, sl in enumerate(self.models.slices):
            rows = np.nonzero(part[sl])[0]
            if len(rows) == 0:
                continue
            cap = _next_pow2(len(rows))
            pad = np.concatenate(
                [rows, np.full(cap - len(rows), rows[0], rows.dtype)])
            plan.append((ci, rows, pad))
        return plan

    def _build_step_args(self, t: int, idx: np.ndarray, plan,
                         catch_up) -> Dict:
        c = self.cfg
        args: Dict[str, Any] = dict(
            t=jnp.asarray(t, jnp.int32),
            idx=jnp.asarray(idx),
            catch_up=jnp.asarray(catch_up, jnp.float32),
            server_params=self.server_params,
            cache=self.cache_g,
            params=[], xs=[], ys=[], train_mask=[], pv=[],
        )
        het = self.scenario.heterogeneity is not None
        if het:
            args["lr_k"], args["steps_k"] = [], []
        for ci, rows, pad in plan:
            args["params"].append(self._store.gather(ci, pad))
            args["xs"].append(jnp.asarray(self.xs_c[ci][pad]))
            args["ys"].append(jnp.asarray(self.ys_c[ci][pad]))
            args["train_mask"].append(
                jnp.asarray(self.train_mask_c[ci][pad]))
            pv = np.zeros(len(pad), bool)
            pv[: len(rows)] = True
            args["pv"].append(jnp.asarray(pv))
            if het:
                args["lr_k"].append(jnp.asarray(self._lr_k_c[ci][pad]))
                args["steps_k"].append(jnp.asarray(self._steps_k_c[ci][pad]))
        if self.prev_teacher is not None:
            pidx, pteach = self.prev_teacher
            args["prev_idx"] = jnp.asarray(pidx)
            args["prev_teacher"] = jnp.asarray(pteach)
        return args

    # ------------------------------------------------------------------
    # O(m) client step: the scan-engine round body on the gathered
    # stack.  Every row with pv=True is a participant, so there is no
    # participation select — padding rows compute redundantly (weight
    # exactly 0.0 in every reduction) and never scatter back.
    # ------------------------------------------------------------------
    def _client_step(self, args: Dict) -> Dict:
        c, s = self.cfg, self.strategy
        t, idx = args["t"], args["idx"]
        kt = jax.random.fold_in(self._key_rounds, t)
        params = args["params"]

        # --- clients: distill on previous teacher, then local training
        if "prev_teacher" in args:
            x_prev = self.x_pub[args["prev_idx"]]
            pteach = args["prev_teacher"]
            params = [
                distill_v(p, x_prev,
                          jnp.broadcast_to(pteach,
                                           (pv.shape[0],) + pteach.shape),
                          c.lr_dist, c.distill_steps)
                for p, pv in zip(params, args["pv"])]
        if self.scenario.heterogeneity is None:
            params = [
                local_train_v(p, x, y, m.astype(jnp.float32),
                              c.lr, c.local_steps)
                for p, x, y, m in zip(params, args["xs"], args["ys"],
                                      args["train_mask"])]
        else:
            decay = jnp.asarray(self._lr_decay, jnp.float32) ** (
                jnp.asarray(t, jnp.float32) - 1.0)
            params = [
                local_train_masked_v(p, x, y, m.astype(jnp.float32),
                                     lr * decay, st, self._max_steps)
                for p, x, y, m, lr, st in zip(
                    params, args["xs"], args["ys"], args["train_mask"],
                    args["lr_k"], args["steps_k"])]

        # --- request list (cache) -------------------------------------
        cache_prev = cache_lib.CacheState(*args["cache"])
        if self.use_cache:
            key_exp = (jax.random.fold_in(jax.random.PRNGKey(c.seed), t)
                       if self.probabilistic_expiry else None)
            miss = cache_lib.miss_mask(cache_prev, idx, t, self.D,
                                       probabilistic=self.probabilistic_expiry,
                                       key=key_exp)
        else:
            miss = jnp.ones(c.public_per_round, bool)
        miss_f = miss.astype(jnp.float32)
        n_req = jnp.sum(miss_f)
        base, base_present = cache_lib.cached_at(cache_prev, idx)

        # --- uplink + aggregation over the gathered stack -------------
        x_round = self.x_pub[idx]
        zs = [predict_v(p, x_round) for p in params]
        z_all = zs[0] if len(zs) == 1 else jnp.concatenate(zs, axis=0)
        z_all = s.transmit(z_all, jax.random.fold_in(kt, TRANSMIT_SALT))
        z_tx = z_all
        pv_all = (args["pv"][0] if len(args["pv"]) == 1
                  else jnp.concatenate(args["pv"]))
        pv_f = pv_all.astype(jnp.float32)
        n_part = jnp.sum(pv_f)
        if self._fused:
            um = s.upload_mask(z_all)
            fbase = (round_kernel.resolve_delta_base(
                         base, base_present, c.public_per_round, c.n_classes)
                     if self._fused_spec["mode"] == "delta" else None)
            fresh = s.aggregate_masked_fused(z_all, pv_f,
                                             self._fused_spec, fbase, t)
        else:
            if not self.codec_up.is_identity:
                z_all = self.codec_up.roundtrip(z_all, base=base,
                                                present=base_present)
            um = s.upload_mask(z_all)
            fresh = s.aggregate_masked(z_all, pv_f, um, t)
        if not self.codec_down.is_identity:
            fresh = self.codec_down.roundtrip(fresh, base=base,
                                              present=base_present)

        # --- assemble teacher + cache update --------------------------
        cache = cache_prev
        if self.use_cache:
            teacher = cache_lib.assemble_teacher(cache_prev, idx, fresh, miss)
            cache, _ = cache_lib.update_global_cache(
                cache_prev, idx, teacher, miss, t)
        else:
            teacher = fresh

        # --- server distillation --------------------------------------
        server_params = distill(args["server_params"], x_round, teacher,
                                c.lr_dist, c.distill_steps)

        # --- communication accounting: the scan engine's expression,
        # evaluated on the identical integer-derived inputs ------------
        n_up = n_req
        if um is not None:  # Selective-FD (float average; allclose only)
            uploaded_total = jnp.sum(
                um.astype(jnp.float32) * pv_f[:, None] * miss_f[None, :])
            n_up = uploaded_total / jnp.maximum(n_part, 1.0)
        uplink, downlink = comm_lib.distillation_round_cost_device(
            n_clients=n_part,
            n_selected=float(c.public_per_round),
            n_up_samples=n_up,
            n_down_samples=n_req,
            n_classes=c.n_classes,
            uplink_bits=s.uplink_bits,
            downlink_bits=s.downlink_bits,
            with_cache_signals=self.use_cache,
            catch_up_down=args["catch_up"],
            bytes_index=c.index_bytes,
            uplink_codec=self.codec_up,
            downlink_codec=self.codec_down,
        )

        out = dict(client_params=params, server_params=server_params,
                   cache=cache, teacher=teacher,
                   uplink=uplink, downlink=downlink)
        if self._telemetry:
            hits, new, expired = obs_device.cache_signal_counts(
                base_present, miss)
            z_srv = z_all
            if self._fused and not self.codec_up.is_identity:
                z_srv = self.codec_up.roundtrip(z_tx, base=base,
                                                present=base_present)
            if self.codec_up.is_identity:
                cerr = jnp.float32(0.0)
            else:
                cerr = obs_device.codec_error_mean(z_srv, z_tx, pv_f, n_part)
            zbar = obs_device.participant_mean(z_srv, pv_f, n_part)
            out.update(
                cache_hits=hits, cache_miss_new=new, cache_expired=expired,
                teacher_entropy_pre=obs_device.mean_entropy(zbar),
                teacher_entropy_post=obs_device.mean_entropy(fresh),
                beta=jnp.asarray(s.sharpen_gauge(zbar, t), jnp.float32),
                codec_quant_error=cerr)
        return out

    # ------------------------------------------------------------------
    def _round(self, t: int, hist: History) -> None:
        part, idx = self._draw_round(t)
        n_part = int(part.sum())
        if n_part == 0:  # total outage: nothing moves, the cache ages
            hist.ledger.record(comm_lib.RoundCost(0.0, 0.0))
            if self._telemetry:
                hist.telemetry.append(obs_device.zeros(self.models.n_cohorts))
            return

        book = self._bookkeeping_jit(self.cache_g, self._get_last_sync_dev(),
                                     jnp.asarray(part),
                                     jnp.asarray(t, jnp.int32))
        plan = self._gather_plan(part)
        args = self._build_step_args(t, idx, plan, book["catch_up"])
        out = self._client_step_jit(args)

        # scatter the valid (non-padding) rows back into the store
        for (ci, rows, _pad), new_p in zip(plan, out["client_params"]):
            n = len(rows)
            self._store.scatter(
                ci, rows,
                jax.tree_util.tree_map(lambda a: a[:n], new_p))
        self.server_params = out["server_params"]
        if self.use_cache:
            self.cache_g = cache_lib.CacheState(*out["cache"])
        self.prev_teacher = (idx, out["teacher"])

        hist.ledger.record(comm_lib.RoundCost(float(out["uplink"]),
                                              float(out["downlink"])))
        if self._telemetry:
            tel = obs_device.RoundTelemetry(
                participants=book["participants"],
                cache_hits=out["cache_hits"],
                cache_miss_new=out["cache_miss_new"],
                cache_expired=out["cache_expired"],
                catch_up_clients=book["catch_up_clients"],
                staleness_hist=book["staleness_hist"],
                uplink_bytes=jnp.asarray(out["uplink"], jnp.float32),
                downlink_bytes=jnp.asarray(out["downlink"], jnp.float32),
                catch_up_bytes=jnp.asarray(book["catch_up"], jnp.float32),
                teacher_entropy_pre=out["teacher_entropy_pre"],
                teacher_entropy_post=out["teacher_entropy_post"],
                beta=out["beta"],
                codec_quant_error=out["codec_quant_error"])
            if self.telemetry_hook is not None:
                tel = self.telemetry_hook(tel, t)
            hist.telemetry.append(tel)
        self._last_sync_dev = book["last_sync"]
        self.last_sync[part] = t

    # ------------------------------------------------------------------
    # Eval + the App.-D proxy teacher: the remaining O(K) compute,
    # chunked through the store on the eval schedule only.
    # ------------------------------------------------------------------
    def _iter_chunks(self):
        for ci in range(self.models.n_cohorts):
            size = self.models.sizes[ci]
            for lo in range(0, size, self._eval_chunk):
                hi = min(lo + self._eval_chunk, size)
                rows = np.arange(lo, hi)
                yield ci, rows, self._store.gather(ci, rows)

    def _teacher_val_full(self) -> jnp.ndarray:
        """Population-mean soft labels on the public validation split —
        the dense engines' ``last_teacher_val``, recomputed lazily from
        current params (it is a pure function of them) instead of every
        round: one chunked O(K) pass at eval/checkpoint time."""
        x_val = self.x_pub[self.pub_val_idx]
        total = jnp.zeros((len(self.pub_val_idx), self.cfg.n_classes),
                          jnp.float32)
        for _ci, _rows, p in self._iter_chunks():
            total = total + jnp.sum(predict_v(p, x_val), axis=0)
        return total / self.cfg.n_clients

    def _eval(self, t: int, hist: History) -> None:
        sa = float(accuracy(self.server_params, jnp.asarray(self.x_test),
                            jnp.asarray(self.y_test),
                            jnp.ones(len(self.y_test))))
        accs = [[] for _ in range(self.models.n_cohorts)]
        vls = []
        for ci, rows, p in self._iter_chunks():
            accs[ci].append(np.asarray(accuracy_v(
                p, jnp.asarray(self.xts_c[ci][rows]),
                jnp.asarray(self.yts_c[ci][rows]),
                jnp.asarray(self.tmask_c[ci][rows], jnp.float32))))
            vls.append(np.asarray(val_loss_hard_v(
                p, jnp.asarray(self.xs_c[ci][rows]),
                jnp.asarray(self.ys_c[ci][rows]),
                jnp.asarray(self.val_mask_c[ci][rows], jnp.float32))))
        accs = [np.concatenate(a) for a in accs]
        hist.rounds.append(t)
        hist.server_acc.append(sa)
        hist.client_acc.append(float(np.mean(np.concatenate(accs))))
        hist.cohort_client_acc.append([float(np.mean(a)) for a in accs])
        hist.cumulative_mb.append(hist.ledger.cumulative_total / 1e6)
        if self.prev_teacher is not None:
            self.last_teacher_val = self._teacher_val_full()
            hist.server_val_loss.append(float(val_loss_soft(
                self.server_params, self.x_pub[self.pub_val_idx],
                self.last_teacher_val)))
        hist.client_val_loss.append(float(np.mean(np.concatenate(vls))))

    # ------------------------------------------------------------------
    # Checkpointing: the shared plumbing works on the store-backed
    # client_params property; teacher_val is recomputed at save time.
    # ------------------------------------------------------------------
    def state_dict(self) -> Dict[str, Any]:
        self.last_teacher_val = (self._teacher_val_full()
                                 if self.prev_teacher is not None else None)
        return super().state_dict()

    def load_state_dict(self, state: Dict[str, Any]) -> None:
        super().load_state_dict(state)
        self._last_sync_dev = jnp.asarray(self.last_sync, jnp.int32)

    # ------------------------------------------------------------------
    # Analyzer entry (repro.analysis.active_checks): the two jitted
    # round-body functions with concrete example arguments, for
    # trace-time scan-safety + K-separation proofs.
    # ------------------------------------------------------------------
    def active_round_fns(self):
        """``[(label, fn, example_args), ...]`` for the bookkeeping and
        gathered client steps (args are concrete; the analyzer traces on
        their shapes)."""
        c = self.cfg
        part, idx = self._draw_round(1)
        if part.sum() == 0:
            part = part.copy()
            part[: min(2, len(part))] = True
        book_args = (self.cache_g, self._get_last_sync_dev(),
                     jnp.asarray(part), jnp.asarray(1, jnp.int32))
        plan = self._gather_plan(part)
        # force the distillation branch so the traced graph covers the
        # full round body (round 1 has no previous teacher)
        saved = self.prev_teacher
        self.prev_teacher = (np.zeros(c.public_per_round, np.int32),
                             jnp.zeros((c.public_per_round, c.n_classes),
                                       jnp.float32))
        try:
            step_args = self._build_step_args(1, idx, plan,
                                              jnp.float32(0.0))
        finally:
            self.prev_teacher = saved
        return [("bookkeeping", self._bookkeeping_step, book_args),
                ("client-step", self._client_step, (step_args,))]
