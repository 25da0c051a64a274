"""Async/buffered aggregation engine: dispatch now, aggregate what arrived.

Every other engine is synchronous-round: the clients drawn in round
``t`` train, upload, and are aggregated in round ``t``.  Production
federated servers do not get that luxury — clients arrive on their own
schedule, train against whatever cache state they were handed, and
report late.  This engine models that regime while staying a single
XLA program (it subclasses :class:`ScannedFederatedDistillation` and
keeps the one-``lax.scan`` structure; the traffic model compiles to
fixed-shape per-round scan inputs, see :mod:`repro.fl.traffic`).

Round semantics (one aggregation window per round):

- **dispatch**: the usual participation draw, restricted to clients
  that are reachable this window (traffic availability + churn) and not
  already in flight.  A dispatched client receives a cache catch-up
  package if it is behind (charged now, against the *pre-round* cache —
  it must train against current state), distills on the previous
  teacher, trains locally, and starts computing its report.  Its
  parameters then stay frozen until the report lands (an in-flight
  client cannot be re-dispatched).
- **arrival**: reports dispatched ``d`` rounds ago (``d`` drawn from
  the traffic latency model) land this window, together with this
  window's zero-delay dispatches.  The server aggregates *whatever
  arrived* through the unchanged two-phase
  ``partial_aggregate``/``finalize_aggregate`` contract, with each
  arriving client's weight multiplied by
  :meth:`Strategy.staleness_weight` of its report staleness (dispatch
  round to now).  Teacher assembly, the global cache update, server
  distillation, and the broadcast all happen at arrival, gated exactly
  like scan's total-outage gate on rounds where nothing arrives.

Ledger rule (the staleness-correct accounting this engine exists for):
a stale reporter's **uplink** is charged at *dispatch-time* cache
state — the client answered the request list it was handed, so its
per-client upload size is the miss count of its dispatch round
(tracked in flight as ``flight_nreq``).  **Catch-up** bytes are charged
against the cache *at the time they flow*: the dispatch side against
the pre-round cache, and the arrival side (entries cached while the
report was in flight) against the cache at arrival —
:func:`repro.core.cache.catch_up_bytes_async`.  ``last_sync`` encodes
the handshake: dispatch marks the client synced through ``t - 1``,
arrival through ``t`` (arrival wins when both happen in one round).

**Byte-identity contract** (the conformance anchor,
``tests/test_engine_conformance.py``): with zero latency, full windows
(``TrafficModel.is_synchronous``), and unit staleness weight
(``staleness_decay == 1``, statically skipped), every mask, draw, and
ledger expression reduces bitwise to the scan engine's — same key
stream, ``arrive == dispatch == part``, an exactly-zero arrival-side
catch-up term, and ``(n_arr * n_req) / n_arr == n_req`` exactly in
IEEE for the per-client upload average.  Staleness *weighting* never
changes the ledger at any latency (weights multiply soft-labels, not
byte counts) — pinned in ``tests/test_traffic.py``.

Telemetry: the per-round row reuses the shared ``_telemetry_row``
expression with the arrival mask as the participant mask and the
pre-round ``last_sync`` — under the dispatch handshake,
``staleness_histogram`` buckets then equal the report delay of each
arrival.  Rounds where nothing arrives record an all-zero row (like
scan's total-outage rounds), even when dispatch-side catch-up bytes
flowed — the ledger, not telemetry, is the byte record.
"""
from __future__ import annotations

from typing import Optional

import jax
import jax.numpy as jnp
import numpy as np

from repro.core import cache as cache_lib
from repro.core import comm as comm_lib
from repro.kernels import round_kernel
from repro.obs import device as obs_device
from repro.obs.trace import stage
from repro.fl.scan_engine import ScannedFederatedDistillation
from repro.fl.strategies.base import TRANSMIT_SALT
from repro.fl.rounds import (
    accuracy,
    accuracy_v,
    distill,
    val_loss_hard_v,
    val_loss_soft,
)
from repro.fl.traffic import TrafficModel

__all__ = ["AsyncFederatedDistillation"]


class AsyncFederatedDistillation(ScannedFederatedDistillation):
    """Buffered-aggregation twin of the scanned engine.

    Same constructor plus ``traffic`` (a
    :class:`repro.fl.traffic.TrafficModel`; the default model — always
    available, zero latency — is the synchronous regime, byte-identical
    to ``engine="scan"``).  The staleness-decay policy rides on the
    strategy: ``STRATEGIES[...](..., staleness_decay=0.9)``.
    """

    def __init__(self, *args, traffic: Optional[TrafficModel] = None,
                 **kwargs):
        super().__init__(*args, **kwargs)
        self.traffic = traffic if traffic is not None else TrafficModel()
        K = self.cfg.n_clients
        # flight state, carried next to last_sync: which clients are
        # mid-report, when each report lands, and the dispatch-time
        # request-list size its uplink will be charged for
        self.in_flight = np.zeros(K, bool)
        self.flight_arrival = np.zeros(K, np.int32)
        self.flight_nreq = np.zeros(K, np.float32)
        # static skip of the staleness multiply: at the default unit
        # decay the aggregation weights are exactly the arrival mask,
        # which keeps the zero-latency metric parity with scan exact
        # rather than "x * 1.0"-shaped
        self._unit_staleness = float(
            self.strategy.opts.get("staleness_decay", 1.0)) == 1.0

    # ------------------------------------------------------------------
    def _round_device(self, carry, xs):
        c, s = self.cfg, self.strategy
        t, offline_t, do_eval, avail_t, delay_t = xs

        # same per-round key stream as scan/host (fold_in by absolute t)
        with stage("cache"):
            kt = jax.random.fold_in(self._key_rounds, t)
            k_idx, k_part = jax.random.split(kt)
            idx = jnp.sort(jax.random.choice(
                k_idx, c.public_size, (c.public_per_round,), replace=False))

            # --- dispatch: scan's participation draw with unreachable
            # and in-flight clients folded into the offline mask
            # (conscription then only recruits clients that could
            # actually start work) ---------------------------------------
            busy = carry["in_flight"]
            blocked = jnp.logical_or(
                offline_t, jnp.logical_or(jnp.logical_not(avail_t), busy))
            dispatch = self.scenario.participation_mask_device(k_part,
                                                               blocked)
            disp_f = dispatch.astype(jnp.float32)
            any_disp = jnp.sum(disp_f) > 0

            # --- arrivals: in-flight reports landing now + zero-delay
            # dispatches (which complete inside their own window) --------
            arrive = jnp.logical_or(
                jnp.logical_and(busy, carry["flight_arrival"] == t),
                jnp.logical_and(dispatch, delay_t == 0))
            arrive_f = arrive.astype(jnp.float32)
            n_arr = jnp.sum(arrive_f)
            any_arr = n_arr > 0

        def gate(new, old):
            """Keep ``old`` wholesale on arrival-free rounds."""
            return jax.tree_util.tree_map(
                lambda a, b: jnp.where(any_arr, a, b), new, old)

        # --- clients: dispatched clients distill on the teacher they
        # were handed, then train locally; params freeze while in flight
        # (an in-flight client is never dispatched, so its report is
        # evaluated from dispatch-time parameters) -----------------------
        cp = carry["client_params"]
        with stage("client_distill"):
            x_prev = self.x_pub[carry["prev_idx"]]
            cp = self._distill_all(
                cp, x_prev, carry["prev_teacher"],
                jnp.logical_and(dispatch, carry["have_prev"]))
        with stage("local_train"):
            cp = self._train_selected(cp, t, dispatch)

        # --- request list at the ARRIVAL round's subset ------------------
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

            # dispatch-updated sync points: staleness of an arrival is
            # the number of rounds its report spent in flight
            ls_mid = jnp.where(dispatch, t - 1, carry["last_sync"])

        # --- staleness-weighted aggregation over ARRIVALS ----------------
        with stage("predict"):
            x_round = self.x_pub[idx]
            z_all = self._predict_all(cp, x_round)
        with stage("aggregate"):
            z_all = s.transmit(z_all, jax.random.fold_in(kt, TRANSMIT_SALT))
            z_tx = z_all
            if self._unit_staleness:
                w = arrive_f
            else:
                w = arrive_f * s.staleness_weight(t - 1 - ls_mid)
            if self._fused:
                um = s.upload_mask(z_all)
                fbase = (round_kernel.resolve_delta_base(
                             base, base_present, c.public_per_round,
                             c.n_classes)
                         if self._fused_spec["mode"] == "delta" else None)
                fresh = s.aggregate_masked_fused(z_all, w, self._fused_spec,
                                                 fbase, t)
            else:
                if not self.codec_up.is_identity:
                    z_all = self.codec_up.roundtrip(z_all, base=base,
                                                    present=base_present)
                um = s.upload_mask(z_all)
                fresh = s.aggregate_masked(z_all, w, um, t)
            if not self.codec_down.is_identity:
                fresh = self.codec_down.roundtrip(fresh, base=base,
                                                  present=base_present)

        # --- teacher + cache + server updates, gated on arrivals ---------
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
            zv = self._predict_all(cp, self.x_pub[self.pub_val_idx])
            teacher_val = jnp.where(any_arr, jnp.mean(zv, axis=0),
                                    carry["teacher_val"])
            have_tv = jnp.logical_or(carry["have_tv"], any_arr)
        with stage("cache"):
            prev_teacher = jnp.where(any_arr, teacher,
                                     carry["prev_teacher"])
            prev_idx = jnp.where(any_arr, idx, carry["prev_idx"])
            have_prev = jnp.logical_or(carry["have_prev"], any_arr)

            # --- ledger: dispatch-time uplink, two-sided catch-up -------
            catch_up = jnp.float32(0.0)
            catch_disp = jnp.float32(0.0)
            if self.use_cache:
                catch_up, catch_disp = cache_lib.catch_up_bytes_async(
                    cache_prev, carry["last_sync"], dispatch, arrive, t)
            # per-arrival upload size is the request-list size of each
            # client's DISPATCH round; the cost model takes the per-client
            # average (exact n_req when everything arrives same-round)
            flight_nreq = jnp.where(dispatch, n_req, carry["flight_nreq"])
            n_up = jnp.sum(arrive_f * flight_nreq) / jnp.maximum(n_arr, 1.0)
            if um is not None:  # Selective-FD gating, applied at arrival
                uploaded_total = jnp.sum(
                    um.astype(jnp.float32) * arrive_f[:, None]
                    * miss_f[None, :])
                n_up = uploaded_total / jnp.maximum(n_arr, 1.0)
            uplink, downlink = comm_lib.distillation_round_cost_device(
                n_clients=n_arr,
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
            )
            uplink = jnp.where(any_arr, uplink, 0.0)
            # dispatch-side sync bytes flow even when nothing arrives
            downlink = jnp.where(any_arr, downlink,
                                 jnp.where(any_disp, catch_disp, 0.0))

            # --- flight + sync bookkeeping ------------------------------
            last_sync = jnp.where(arrive, t, ls_mid)
            in_flight = jnp.logical_or(
                jnp.logical_and(busy, jnp.logical_not(arrive)),
                jnp.logical_and(dispatch, delay_t > 0))
            flight_arrival = jnp.where(dispatch, t + delay_t,
                                       carry["flight_arrival"])

        # --- telemetry: arrivals are the participants; pre-round
        # last_sync makes staleness buckets equal report delay ------------
        tel = None
        if self._telemetry:
            z_srv = z_all
            if self._fused and not self.codec_up.is_identity:
                z_srv = self.codec_up.roundtrip(z_tx, base=base,
                                                present=base_present)
            tel = obs_device.gate(self._telemetry_row(
                t=t, part_full=arrive, miss=miss, base_present=base_present,
                z_tx=z_tx, z_srv=z_srv, fresh=fresh,
                last_sync=carry["last_sync"], uplink=uplink,
                downlink=downlink, catch_up=catch_up), any_arr)

        # --- eval (scheduled rounds only) --------------------------------
        def _eval():
            with stage("eval"):
                sa = accuracy(server_params, self.x_test, self.y_test,
                              jnp.ones(len(self.y_test)))
                accs = [accuracy_v(p, self.xts_c[i], self.yts_c[i],
                                   self.tmask_c[i].astype(jnp.float32))
                        for i, p in enumerate(cp)]
                ca = jnp.mean(self.models.concat(accs))
                cacc = jnp.stack([jnp.mean(a) for a in accs])
                sv = val_loss_soft(server_params,
                                   self.x_pub[self.pub_val_idx], teacher_val)
                cv = jnp.mean(self.models.concat(
                    [val_loss_hard_v(p, self.xs_c[i], self.ys_c[i],
                                     self.val_mask_c[i].astype(jnp.float32))
                     for i, p in enumerate(cp)]))
                return sa, ca, sv, cv, cacc

        sa, ca, sv, cv, cacc = jax.lax.cond(
            do_eval, _eval,
            lambda: (jnp.float32(0),) * 4
            + (jnp.zeros(self.models.n_cohorts, jnp.float32),))

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
            in_flight=in_flight,
            flight_arrival=flight_arrival,
            flight_nreq=flight_nreq,
        )
        ys = dict(uplink=uplink, downlink=downlink,
                  server_acc=sa, client_acc=ca, server_val=sv, client_val=cv,
                  cohort_acc=cacc, have_tv=have_tv)
        if tel is not None:
            ys["telemetry"] = tel
        return new_carry, ys

    # ------------------------------------------------------------------
    def _aot_args(self, ts, offline, do_eval):
        carry, (ts_x, off_x, ev_x) = super()._aot_args(ts, offline, do_eval)
        ts_np = np.asarray(ts)
        start = int(ts_np[0]) if ts_np.size else self.t_done + 1
        compiled = self.traffic.compile(int(ts_np.size), self.cfg.n_clients,
                                        start=start)
        return (carry, (ts_x, off_x, ev_x,
                        jnp.asarray(compiled.available),
                        jnp.asarray(compiled.delay)))

    # ------------------------------------------------------------------
    # flight state joins the checkpointable carry next to last_sync
    # (state_dict feeds _initial_carry, so the scan carry extends
    # automatically and chained/restored runs keep reports in flight)
    # ------------------------------------------------------------------
    def state_dict(self):
        state = super().state_dict()
        state["in_flight"] = jnp.asarray(self.in_flight, bool)
        state["flight_arrival"] = jnp.asarray(self.flight_arrival, jnp.int32)
        state["flight_nreq"] = jnp.asarray(self.flight_nreq, jnp.float32)
        return state

    def load_state_dict(self, state) -> None:
        super().load_state_dict(state)
        self.in_flight = np.asarray(state["in_flight"]).astype(bool)
        self.flight_arrival = np.asarray(
            state["flight_arrival"]).astype(np.int32)
        self.flight_nreq = np.asarray(state["flight_nreq"]).astype(np.float32)

    def _finish_run(self, carry, ys, eval_np, t0):
        self.in_flight = np.asarray(carry["in_flight"]).astype(bool)
        self.flight_arrival = np.asarray(
            carry["flight_arrival"]).astype(np.int32)
        self.flight_nreq = np.asarray(carry["flight_nreq"]).astype(np.float32)
        return super()._finish_run(carry, ys, eval_np, t0)
