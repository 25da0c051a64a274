"""Generic round loop + jitted client primitives.

The client axis is fully vmapped *per cohort*: client parameters are a
short static list of stacked pytrees (one per model cohort, see
:mod:`repro.fl.cohorts`; a homogeneous run is a one-element list whose
ops are bit-identical to a single stack), private shards are dense
``(K, n_max)`` arrays with validity masks, and every per-client
primitive below is a single jitted program over each cohort's axis — a
200-client scenario sweep runs without any Python loop over clients.
Scenario heterogeneity (per-client local-step counts / learning rates)
stays vmapped too, via ``local_train_masked``: every client scans the
same ``max_steps`` and masks out its tail steps.

Workflow per round t (SCARLET Alg. 1, any participation scenario):
  1. server picks the public subset P^t and computes the request list
     (cache miss mask) when caching is enabled;
  2. participating clients distill on the *previous* round's teacher
     (z-hat^{t-1}), then train locally on their private shard;
  3. clients emit soft-labels for requested samples (uplink);
  4. server aggregates via the round's Strategy, assembles the teacher
     from fresh + cached entries, updates the global cache and signals,
     distills the server model;
  5. the communication ledger records exact uplink/downlink bytes,
     including cache signals and catch-up packages for stale clients.

Cache semantics follow Alg. 3 (expiry checked at request time); see
``repro.core.cache`` and ``src/repro/fl/README.md``.
"""
from __future__ import annotations

import functools
from dataclasses import dataclass, field
from typing import Any, Dict, List, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from repro.compress import get_codec
from repro.core import cache as cache_lib
from repro.core import comm as comm_lib
from repro.obs import device as obs_device
from repro.data.synthetic import (
    dirichlet_partition,
    make_public_private,
    pad_client_shards,
    uniform_client_shards,
)
from repro.fl.cohorts import ClientModels, resolve_cohorts
from repro.fl.config import FLConfig
from repro.fl.scenarios import Scenario
from repro.fl.strategies import base as strat_base
from repro.fl.strategies.base import Strategy
from repro.kernels import mlp_distill_kernel
from repro.models.resnet import apply_mlp, init_mlp


# ---------------------------------------------------------------------------
# jitted per-client primitives
# ---------------------------------------------------------------------------

def _ce(params, x, y, mask):
    logits = apply_mlp(params, x)
    logp = jax.nn.log_softmax(logits, axis=-1)
    nll = -jnp.take_along_axis(logp, y[:, None], axis=-1)[:, 0]
    return jnp.sum(nll * mask) / jnp.maximum(jnp.sum(mask), 1.0)


def _kl(params, x, teacher):
    logits = apply_mlp(params, x)
    logp = jax.nn.log_softmax(logits, axis=-1)
    t = jnp.clip(teacher, 1e-12, 1.0)
    return jnp.mean(jnp.sum(t * (jnp.log(t) - logp), axis=-1))


@functools.partial(jax.jit, static_argnames=("steps",))
def local_train(params, x, y, mask, lr, steps: int):
    def body(p, _):
        g = jax.grad(_ce)(p, x, y, mask)
        return jax.tree_util.tree_map(lambda a, b: a - lr * b, p, g), None

    params, _ = jax.lax.scan(body, params, None, length=steps)
    return params


@functools.partial(jax.jit, static_argnames=("max_steps",))
def local_train_masked(params, x, y, mask, lr, n_steps, max_steps: int):
    """Heterogeneous-schedule variant: runs ``max_steps`` gradient steps
    but applies only the first ``n_steps`` (per-client, dynamic).  vmap
    this with per-client ``lr``/``n_steps`` arrays to give every client
    its own schedule inside one jitted program."""

    def body(p, i):
        g = jax.grad(_ce)(p, x, y, mask)
        step = jnp.where(i < n_steps, lr, 0.0)
        return jax.tree_util.tree_map(lambda a, b: a - step * b, p, g), None

    params, _ = jax.lax.scan(body, params, jnp.arange(max_steps))
    return params


@functools.partial(jax.jit, static_argnames=("steps",))
def distill(params, x, teacher, lr, steps: int):
    def body(p, _):
        g = jax.grad(_kl)(p, x, teacher)
        return jax.tree_util.tree_map(lambda a, b: a - lr * b, p, g), None

    params, _ = jax.lax.scan(body, params, None, length=steps)
    return params


@jax.jit
def predict_soft(params, x):
    return jax.nn.softmax(apply_mlp(params, x), axis=-1)


@jax.jit
def val_loss_soft(params, x, teacher):
    """Server-side proxy metric (App. D): distillation loss on a held-out
    public validation split — no test labels needed."""
    return _kl(params, x, teacher)


@jax.jit
def val_loss_hard(params, x, y, mask):
    """Client-side proxy metric (App. D): CE on a held-out private
    validation split."""
    return _ce(params, x, y, mask)


@jax.jit
def accuracy(params, x, y, mask):
    pred = jnp.argmax(apply_mlp(params, x), axis=-1)
    ok = (pred == y) * mask
    return jnp.sum(ok) / jnp.maximum(jnp.sum(mask), 1.0)


val_loss_hard_v = jax.vmap(val_loss_hard, in_axes=(0, 0, 0, 0))
local_train_v = jax.vmap(local_train, in_axes=(0, 0, 0, 0, None, None))
local_train_masked_v = jax.vmap(local_train_masked,
                                in_axes=(0, 0, 0, 0, 0, 0, None))
distill_v = jax.vmap(distill, in_axes=(0, None, 0, None, None))
predict_v = jax.vmap(predict_soft, in_axes=(0, None))
accuracy_v = jax.vmap(accuracy, in_axes=(0, 0, 0, 0))


def _select(new, old, keep_mask):
    """Per-client parameter update gating (partial participation)."""
    def sel(a, b):
        m = keep_mask.reshape((-1,) + (1,) * (a.ndim - 1))
        return jnp.where(m, a, b)

    return jax.tree_util.tree_map(sel, new, old)


def _select_cohorts(new, old, masks):
    """``_select`` over per-cohort param lists (masks pre-split)."""
    return [_select(n, o, m) for n, o, m in zip(new, old, masks)]


# ---------------------------------------------------------------------------
# History
# ---------------------------------------------------------------------------

@dataclass
class History:
    rounds: List[int] = field(default_factory=list)
    server_acc: List[float] = field(default_factory=list)
    client_acc: List[float] = field(default_factory=list)
    cumulative_mb: List[float] = field(default_factory=list)
    # Appendix-D proxy metrics (no test labels required in deployment)
    server_val_loss: List[float] = field(default_factory=list)
    client_val_loss: List[float] = field(default_factory=list)
    # per-cohort mean client accuracy, one row per eval round (a single
    # column for homogeneous runs) — see repro.fl.cohorts
    cohort_client_acc: List[List[float]] = field(default_factory=list)
    ledger: comm_lib.CommLedger = field(default_factory=comm_lib.CommLedger)
    # Final accuracies are ``None`` when the leg never evaluated that
    # model (a zero-round leg, or Individual's nonexistent server) —
    # "not evaluated" must stay distinguishable from a measured 0.0,
    # since benchmarks read these as real accuracies.
    final_server_acc: Optional[float] = None
    final_client_acc: Optional[float] = None
    # per-round device-plane telemetry (repro.obs.device.TelemetryLog)
    # when the run had FLConfig.telemetry on; None otherwise.  Not part
    # of state_dict: telemetry is a per-run-leg observation, like the
    # ledger.
    telemetry: Optional[obs_device.TelemetryLog] = None

    def as_dict(self) -> Dict[str, Any]:
        out = {
            "rounds": self.rounds,
            "server_acc": self.server_acc,
            "client_acc": self.client_acc,
            "cumulative_mb": self.cumulative_mb,
            "server_val_loss": self.server_val_loss,
            "client_val_loss": self.client_val_loss,
            "cohort_client_acc": self.cohort_client_acc,
            "comm": self.ledger.summary(),
            "final_server_acc": self.final_server_acc,
            "final_client_acc": self.final_client_acc,
        }
        if self.telemetry is not None:
            out["telemetry"] = self.telemetry.as_dict()
        return out


# ---------------------------------------------------------------------------
# Engine
# ---------------------------------------------------------------------------

class FederatedDistillation:
    """Generic distillation-based FL run (DS-FL / SCARLET / CFD / COMET /
    Selective-FD / mean), with optional soft-label caching (drop-in for
    any strategy — paper Fig. 11) and arbitrary client scenarios
    (participation sampling, outages, heterogeneous schedules).

    RNG streams are split by concern: ``rng_idx`` drives public-subset
    selection, ``rng_part`` drives participation sampling, ``rng``
    remains for strategy payload transforms.  Runs that differ only in
    scenario therefore see identical P^t sequences, making their
    communication ledgers directly comparable.

    ``track_local_caches=True`` additionally maintains every client's
    mirrored local cache (signals + queue for participants, catch-up
    packages for returning stragglers) so tests can assert the Alg. 2/3
    byte-identity invariant; it is off by default because the simulation
    itself only needs the global cache.

    ``rng_backend="jax"`` draws the P^t subsets and participation masks
    from a split jax key stream instead of the numpy Generators — the
    exact same stream the scanned engine
    (:class:`repro.fl.scan_engine.ScannedFederatedDistillation`) folds
    on-device, which is what makes host-loop and scanned runs directly
    comparable (the parity suite relies on it).

    Wire codecs (``cfg.uplink_codec`` / ``cfg.downlink_codec``,
    :mod:`repro.compress`) apply the lossy encode->decode round trip to
    what each direction actually carries — client soft-labels after
    ``Strategy.transmit`` on the uplink, the freshly aggregated teacher
    on the downlink — and switch the ledger to the codec's analytic
    payload bytes.  The decoded downlink teacher is also what the server
    distills on and what enters the global cache, keeping server and
    client caches bit-identical (clients can only cache what the wire
    delivered).
    """

    def __init__(self, cfg: FLConfig, strategy: Strategy,
                 cache_duration: int = 0, use_cache: Optional[bool] = None,
                 probabilistic_expiry: bool = False,
                 scenario: Optional[Scenario] = None,
                 track_local_caches: bool = False,
                 rng_backend: str = "numpy"):
        self.cfg = cfg
        self.strategy = strategy
        self.D = cache_lib.normalize_cache_duration(cache_duration)
        self.probabilistic_expiry = probabilistic_expiry
        self.use_cache = strategy.uses_cache if use_cache is None else use_cache
        if self.D == 0:
            self.use_cache = self.use_cache and False
        self.scenario = scenario or Scenario.from_participation_rate(cfg.participation)
        self.track_local_caches = track_local_caches
        if rng_backend not in ("numpy", "jax"):
            raise ValueError(f"unknown rng_backend: {rng_backend!r}")
        self.rng_backend = rng_backend
        self.codec_up = get_codec(cfg.uplink_codec,
                                  index_bytes=cfg.index_bytes)
        self.codec_down = get_codec(cfg.downlink_codec,
                                    index_bytes=cfg.index_bytes)
        self.rng = np.random.default_rng(cfg.seed)
        self.rng_idx = np.random.default_rng([cfg.seed, 17])
        self.rng_part = np.random.default_rng([cfg.seed, 29])
        # device-plane telemetry (repro.obs): per-round counters/gauges
        # appended to History.telemetry.  telemetry_hook is an optional
        # pure-jnp transform (tel, t) -> tel applied inside the round
        # body — it must be scan-safe; repro.analysis flags hooks that
        # smuggle host callbacks into the compiled round.
        self._telemetry = bool(cfg.telemetry)
        self.telemetry_hook = None
        # clients per round whose distillation the traced round runs in
        # the VMEM kernel (set by _distill_all when the round is traced)
        self.distill_kernel_clients = 0
        # client slots per round that the traced round's local training
        # runs (set by _train_selected; the host loop trains every client)
        self.local_train_clients = cfg.n_clients
        self._setup()

    # ------------------------------------------------------------------
    # Placement/init hooks: the active-set engine
    # (repro.fl.active_engine) overrides these to keep O(K)-sized
    # per-client state on the host; for the dense engines they are the
    # identity of the historical code, so traced programs (and golden
    # ledgers) are untouched.
    # ------------------------------------------------------------------
    def _client_array(self, x):
        """Placement for O(K) per-client data arrays (one row per
        client: private/test shards, masks, per-client schedules)."""
        return jnp.asarray(x)

    def _eval_array(self, x):
        """Placement for eval-only arrays whose size tracks the
        population (the held-out test set is ``~private_size/5``)."""
        return jnp.asarray(x)

    def _init_client_params(self, keys) -> None:
        """Materialize per-client parameters from the ``(K, ...)``
        stacked key slice (one key per client, global order)."""
        self.client_params = self.models.init_params(keys)

    def _partition_clients(self, x, y, seed: int):
        """Per-client shards in the dense ``(xs, ys, mask)`` layout."""
        c = self.cfg
        if c.partition == "uniform":
            return uniform_client_shards(x, y, c.n_clients)
        if c.partition != "dirichlet":
            raise ValueError(f"unknown partition {c.partition!r} "
                             "(want 'dirichlet' or 'uniform')")
        parts = dirichlet_partition(y, c.n_clients, c.alpha, seed=seed)
        return pad_client_shards(x, y, parts)

    # ------------------------------------------------------------------
    def _setup(self) -> None:
        c = self.cfg
        data = make_public_private(c.private_size, c.public_size, c.n_classes,
                                   c.dim, seed=c.seed,
                                   cluster_scale=c.cluster_scale, noise=c.noise)
        self.data = data
        self.xs, self.ys, self.mask = map(
            self._client_array,
            self._partition_clients(data["x_private"], data["y_private"],
                                    seed=c.seed))
        self.xts, self.yts, self.tmask = map(
            self._client_array,
            self._partition_clients(data["x_test"], data["y_test"],
                                    seed=c.seed + 7))
        self.x_pub = jnp.asarray(data["x_public"])
        self.x_test = self._eval_array(data["x_test"])
        self.y_test = self._eval_array(data["y_test"])

        # Client-model cohorts: client_params is a LIST with one stacked
        # pytree per cohort (architectures differ, so one stacked tree is
        # impossible); a homogeneous config yields a one-element list
        # whose ops are bit-identical to the legacy single-stack path.
        # Clients keep their global key regardless of the cohort split.
        self.models = ClientModels(resolve_cohorts(c), c.dim, c.n_classes)
        key = jax.random.PRNGKey(c.seed)
        keys = jax.random.split(key, c.n_clients + 1)
        self._init_client_params(keys[:-1])
        self.server_params = init_mlp(keys[-1], c.dim, c.n_classes, c.hidden, c.mlp_depth)

        # Appendix-D validation splits: 10% of public for the server proxy,
        # 10% of each client's private shard for the client proxy
        n_pub_val = max(c.public_size // 10, 10)
        self.pub_val_idx = jnp.asarray(
            np.random.default_rng(c.seed + 99).choice(
                c.public_size, n_pub_val, replace=False))
        val_cut = jnp.maximum((jnp.sum(self.mask, 1) * 0.9).astype(jnp.int32), 1)
        pos = jnp.arange(self.mask.shape[1])[None, :]
        self.val_mask = self._client_array(
            jnp.logical_and(self.mask, pos >= val_cut[:, None]))
        self.train_mask = self._client_array(
            jnp.logical_and(self.mask, pos < val_cut[:, None]))
        # per-cohort views of every per-client array (identity for a
        # single cohort); the data partition itself is cohort-agnostic
        m = self.models
        self.xs_c, self.ys_c = m.split(self.xs), m.split(self.ys)
        self.train_mask_c = m.split(self.train_mask)
        self.val_mask_c = m.split(self.val_mask)
        self.xts_c, self.yts_c = m.split(self.xts), m.split(self.yts)
        self.tmask_c = m.split(self.tmask)
        self.last_teacher_val: Optional[jnp.ndarray] = None

        self.cache_g = cache_lib.init_cache(c.public_size, c.n_classes)
        self.local_caches: List[cache_lib.CacheState] = [
            cache_lib.init_cache(c.public_size, c.n_classes)
            for _ in range(c.n_clients)
        ] if self.track_local_caches else []
        self.prev_teacher: Optional[Tuple[np.ndarray, jnp.ndarray]] = None  # (idx, z)
        self.last_sync = np.full(c.n_clients, 0, np.int64)  # last participated round
        self.t_done = 0  # rounds completed so far (run() continues from here)
        self.n_params = sum(x.size for x in jax.tree_util.tree_leaves(self.server_params))
        # per-round key stream shared with the scanned engine (jax mode)
        self._key_rounds = jax.random.fold_in(jax.random.PRNGKey(c.seed), 43)

        het = self.scenario.heterogeneity
        if het is not None:
            lr_k, steps_k, max_steps = het.resolve(c.n_clients, c.lr, c.local_steps)
            self._lr_k = self._client_array(jnp.asarray(lr_k, jnp.float32))
            self._steps_k = self._client_array(jnp.asarray(steps_k, jnp.int32))
            self._lr_k_c = self.models.split(self._lr_k)
            self._steps_k_c = self.models.split(self._steps_k)
            self._max_steps = max_steps
            self._lr_decay = het.lr_decay

    # ------------------------------------------------------------------
    def run(self, rounds: Optional[int] = None) -> History:
        """Run ``rounds`` more rounds (default: the configured count).

        Rounds are numbered absolutely: a second ``run()`` — or a run on
        an engine restored via :meth:`load_state_dict` — continues at
        ``t_done + 1`` with the *same* per-round key stream a single
        uninterrupted run would have used, so split runs are bit-
        identical to unsplit ones per round (``tests/test_checkpoint.py``).
        Each ``run()`` returns a *fresh* :class:`History`, so cumulative
        quantities (``ledger`` totals, ``cumulative_mb``) cover only that
        leg — stitch legs by concatenating their ledgers, as the
        checkpoint tests do; the ledger is not part of ``state_dict``.
        """
        c = self.cfg
        hist = History()
        if self._telemetry:
            hist.telemetry = obs_device.TelemetryLog()
        # ``rounds=0`` is an honest zero-round leg (useful for state-only
        # restarts), not a fall-through to the full configured run
        T = c.rounds if rounds is None else rounds
        t_end = self.t_done + T
        for t in range(self.t_done + 1, t_end + 1):
            self._round(t, hist)
            if t % c.eval_every == 0 or t == t_end:
                self._eval(t, hist)
        self.t_done = t_end
        hist.final_server_acc = hist.server_acc[-1] if hist.server_acc else None
        hist.final_client_acc = hist.client_acc[-1] if hist.client_acc else None
        return hist

    # ------------------------------------------------------------------
    # Checkpointing: the engine state that evolves across rounds, as one
    # fixed-structure pytree (repro.checkpoint.save_pytree-compatible).
    # ------------------------------------------------------------------
    def state_dict(self) -> Dict[str, Any]:
        """Snapshot of all cross-round simulation state.

        Covers params, cache, sync bookkeeping, the previous-round
        teacher, and the round counter — everything ``run()`` reads that
        a fresh engine would not reconstruct from the config.  The
        structure is fixed (absent optionals become zero placeholders +
        ``have_*`` flags) so ``checkpoint.load_pytree`` can use a fresh
        engine's ``state_dict()`` as the ``like`` tree.  Mirrored local
        caches (``track_local_caches``, a host-only verification mode)
        are not included, and neither are the legacy stateful numpy
        Generators — bit-identical continuation therefore requires the
        stateless ``rng_backend="jax"`` key stream (any engine).
        """
        c = self.cfg
        m = c.public_per_round
        if self.prev_teacher is not None:
            pidx, pteach = self.prev_teacher
            if jnp.ndim(pteach) == 3:
                # per-client (K, m, N) teachers (COMET) don't fit the
                # fixed (m, N) slot a fresh engine's like-tree declares,
                # so the npz round trip would fail on restore — reject
                # at save time with a diagnosable error instead
                raise ValueError(
                    "per-client prev_teacher stacks (COMET) are not "
                    "checkpointable; state_dict supports shared-teacher "
                    "strategies only")
            prev_idx = jnp.asarray(pidx, jnp.int32)
            prev_teacher = jnp.asarray(pteach, jnp.float32)
            have_prev = jnp.asarray(True)
        else:
            prev_idx = jnp.zeros((m,), jnp.int32)
            prev_teacher = jnp.zeros((m, c.n_classes), jnp.float32)
            have_prev = jnp.asarray(False)
        if self.last_teacher_val is not None:
            teacher_val = jnp.asarray(self.last_teacher_val, jnp.float32)
            have_tv = jnp.asarray(True)
        else:
            teacher_val = jnp.zeros((len(self.pub_val_idx), c.n_classes),
                                    jnp.float32)
            have_tv = jnp.asarray(False)
        return dict(
            t_done=jnp.asarray(self.t_done, jnp.int32),
            client_params=self.client_params,
            server_params=self.server_params,
            cache=self.cache_g,
            prev_idx=prev_idx,
            prev_teacher=prev_teacher,
            have_prev=have_prev,
            teacher_val=teacher_val,
            have_tv=have_tv,
            last_sync=jnp.asarray(self.last_sync, jnp.int32),
        )

    def load_state_dict(self, state: Dict[str, Any]) -> None:
        """Restore a :meth:`state_dict` snapshot; the next ``run()``
        continues bit-identically to an uninterrupted run."""
        if self.rng_backend != "jax":
            # the numpy Generators are stateful and not captured by
            # state_dict — a restored numpy-backend run would silently
            # replay virgin streams and diverge from the original
            raise ValueError(
                "restoring requires the stateless rng_backend='jax' key "
                "stream (construct the engine with rng_backend='jax')")
        if self.track_local_caches:
            # mirrored per-client caches are not captured either: a
            # restored engine would verify cold mirrors against a warm
            # global cache and report false divergence
            raise ValueError(
                "track_local_caches state is not checkpointed; restore "
                "into an engine with track_local_caches=False")
        self.t_done = int(state["t_done"])
        self.client_params = state["client_params"]
        self.server_params = state["server_params"]
        self.cache_g = cache_lib.CacheState(*state["cache"])
        self.prev_teacher = ((np.asarray(state["prev_idx"]),
                              jnp.asarray(state["prev_teacher"]))
                             if bool(state["have_prev"]) else None)
        self.last_teacher_val = (jnp.asarray(state["teacher_val"])
                                 if bool(state["have_tv"]) else None)
        self.last_sync = np.asarray(state["last_sync"]).astype(np.int64)

    # ------------------------------------------------------------------
    def _distill_all(self, params, x_prev, pteach, keep):
        """Per-cohort client distillation of the clients in ``keep``
        (``(K,)`` bool; the others keep their params) on a shared
        ``(m, N)`` teacher or per-client ``(K, m, N)`` teacher stack
        (COMET).

        A cohort whose shapes the VMEM kernel takes on this backend
        (``mlp_distill_kernel.use_kernel``) runs ``mlp_distill``; every
        other cohort runs ``distill_v``.  The number of clients the
        kernel takes is recorded when the round is traced
        (``distill_kernel_clients``)."""
        c = self.cfg
        keep_c = self.models.split(keep)
        per_client = jnp.ndim(pteach) == 3
        teach_c = self.models.split(pteach) if per_client else None
        out, n_kernel = [], 0
        for i, p in enumerate(params):
            t = teach_c[i] if per_client else pteach
            if mlp_distill_kernel.use_kernel(p, x_prev.shape[0]):
                out.append(mlp_distill_kernel.mlp_distill(
                    p, x_prev, t, keep_c[i], lr=c.lr_dist,
                    steps=c.distill_steps))
                n_kernel += self.models.sizes[i]
            else:
                if not per_client:
                    t = jnp.broadcast_to(t, (self.models.sizes[i],) + t.shape)
                out.append(_select(distill_v(p, x_prev, t, c.lr_dist,
                                             c.distill_steps), p, keep_c[i]))
        self.distill_kernel_clients = n_kernel
        return out

    def _predict_all(self, params, x):
        """Cohort-collapsing soft predictions: ``(K, |x|, N)`` in global
        client order — the boundary where architecture heterogeneity
        becomes invisible to strategies/codecs/cache/ledger."""
        return self.models.concat([predict_v(p, x) for p in params])

    # ------------------------------------------------------------------
    def _local_train_all(self, params, t, rows=None):
        """Per-cohort local training over the ``params`` list.  ``t``
        may be a python int (host loop) or traced (scan).  ``rows[i]``,
        where given and not ``None``, holds the client indices of cohort
        ``i`` to train: those clients' params, shards and schedules are
        gathered (indices past the cohort clip to its last client) and
        the cohort's result is that block."""
        c = self.cfg
        rows = rows or [None] * len(params)

        def take(a, r):
            return a if r is None else jnp.take(a, r, axis=0, mode="clip")

        def args(i, p):
            r = rows[i]
            return (jax.tree_util.tree_map(lambda a: take(a, r), p),
                    take(self.xs_c[i], r), take(self.ys_c[i], r),
                    take(self.train_mask_c[i], r).astype(jnp.float32))

        if self.scenario.heterogeneity is None:
            return [local_train_v(*args(i, p), c.lr, c.local_steps)
                    for i, p in enumerate(params)]
        decay = jnp.asarray(self._lr_decay, jnp.float32) ** (
            jnp.asarray(t, jnp.float32) - 1.0)
        return [local_train_masked_v(*args(i, p),
                                     take(self._lr_k_c[i], rows[i]) * decay,
                                     take(self._steps_k_c[i], rows[i]),
                                     self._max_steps)
                for i, p in enumerate(params)]

    def _train_selected(self, params, t, keep):
        """Local training of the clients in ``keep`` (``(K,)`` bool); the
        others keep their params bit for bit.

        Per cohort, at most ``cap_i = min(Scenario.max_participants(K),
        n_i)`` clients can take part.  Where ``cap_i`` is the cohort's
        size every client trains and ``_select`` keeps the participants'
        results (the train-all program, unchanged).  Otherwise the
        participants are gathered into a block of ``cap_i`` slots, the
        block trains, and its rows are scattered back; a slot left empty
        (a round with fewer participants) trains a duplicate and writes
        nothing.  Local training draws no random numbers, so either way
        each participant's update is the same.  The number of client
        slots trained is recorded when the round is traced
        (``local_train_clients``)."""
        sizes = self.models.sizes
        cap = self.scenario.max_participants(self.cfg.n_clients)
        caps = [min(cap, n) for n in sizes]
        self.local_train_clients = sum(caps)
        rows = [None if cap_i == n
                else jnp.nonzero(self.models.split(keep)[i], size=cap_i,
                                 fill_value=n)[0]
                for i, (cap_i, n) in enumerate(zip(caps, sizes))]
        upd = self._local_train_all(params, t, rows)
        # split after training, so that at full capacity the program is
        # _select_cohorts(_local_train_all(...)) in the same order
        keep_c = self.models.split(keep)
        return [_select(u, p, k) if r is None
                else jax.tree_util.tree_map(
                    lambda a, b, r=r: a.at[r].set(b, mode="drop"), p, u)
                for u, p, k, r in zip(upd, params, keep_c, rows)]

    # ------------------------------------------------------------------
    def _draw_round(self, t: int) -> Tuple[np.ndarray, np.ndarray]:
        """(participation mask, sorted P^t indices) for round ``t``.

        numpy mode: two dedicated Generators (legacy stream).  jax mode:
        the per-round fold of ``_key_rounds`` — identical draws to the
        scanned engine's on-device sampling.
        """
        c = self.cfg
        K = c.n_clients
        if self.rng_backend == "jax":
            kt = jax.random.fold_in(self._key_rounds, t)
            k_idx, k_part = jax.random.split(kt)
            idx = np.asarray(jnp.sort(jax.random.choice(
                k_idx, c.public_size, (c.public_per_round,), replace=False)))
            part = np.asarray(self.scenario.participation_mask_device(
                k_part, jnp.asarray(self.scenario.offline_mask(t, K))))
            return part, idx
        part = self.scenario.participation_mask(t, K, self.rng_part)
        # P^t is drawn from its own stream *before* any participation
        # branching so every scenario sees the identical subset sequence.
        idx = np.sort(self.rng_idx.choice(c.public_size, c.public_per_round,
                                          replace=False))
        return part, idx

    # ------------------------------------------------------------------
    def _telemetry_row(self, *, t, part_full, miss, base_present, z_tx,
                       z_srv, fresh, last_sync, uplink, downlink, catch_up,
                       axis_name: Optional[str] = None,
                       part_local=None) -> obs_device.RoundTelemetry:
        """One :class:`repro.obs.device.RoundTelemetry` row.

        Shared by all three engines — the single expression is what
        makes the counter stacks byte-equal by construction.  Integer
        counters derive from the REPLICATED full-width inputs
        (``part_full``, the pre-update ``miss``/``base_present``/
        ``last_sync``); participant-mean gauges use the (possibly
        shard-local) ``z``/``part_local`` with a psum over
        ``axis_name`` on the sharded engine.  ``z_tx`` is the stack as
        transmitted, ``z_srv`` the server's post-uplink-codec view,
        ``fresh`` the aggregated teacher after sharpening and the
        downlink codec.
        """
        part_f = jnp.asarray(
            part_local if part_local is not None else part_full,
            jnp.float32)
        n_part = jnp.sum(jnp.asarray(part_full, jnp.float32))
        hits, new, expired = obs_device.cache_signal_counts(
            base_present, miss)
        if self.codec_up.is_identity:
            cerr = jnp.float32(0.0)
        else:
            cerr = obs_device.codec_error_mean(z_srv, z_tx, part_f, n_part,
                                               axis_name=axis_name)
        zbar = obs_device.participant_mean(z_srv, part_f, n_part,
                                           axis_name=axis_name)
        tel = obs_device.RoundTelemetry(
            participants=obs_device.participants_per_cohort(
                part_full, self.models.offsets, self.models.sizes),
            cache_hits=hits, cache_miss_new=new, cache_expired=expired,
            catch_up_clients=obs_device.returning_client_count(
                part_full, last_sync, t),
            staleness_hist=obs_device.staleness_histogram(
                part_full, last_sync, t),
            uplink_bytes=jnp.asarray(uplink, jnp.float32),
            downlink_bytes=jnp.asarray(downlink, jnp.float32),
            catch_up_bytes=jnp.asarray(catch_up, jnp.float32),
            teacher_entropy_pre=obs_device.mean_entropy(zbar),
            teacher_entropy_post=obs_device.mean_entropy(fresh),
            beta=jnp.asarray(self.strategy.sharpen_gauge(zbar, t),
                             jnp.float32),
            codec_quant_error=cerr)
        if self.telemetry_hook is not None:
            tel = self.telemetry_hook(tel, t)
        return tel

    # ------------------------------------------------------------------
    def _round(self, t: int, hist: History) -> None:
        c, s = self.cfg, self.strategy
        K = c.n_clients
        part, idx = self._draw_round(t)
        n_part = int(part.sum())
        idx_j = jnp.asarray(idx)

        if n_part == 0:  # total outage: nothing moves, the cache ages
            hist.ledger.record(comm_lib.RoundCost(0.0, 0.0))
            if self._telemetry:  # all-zero row, matching the device
                # engines' gated (zeroed) telemetry on outage rounds
                hist.telemetry.append(obs_device.zeros(self.models.n_cohorts))
            return
        part_j = jnp.asarray(part)

        # --- clients: distill on previous teacher, then local training ----
        part_c = self.models.split(part_j)
        new_params = self.client_params
        if self.prev_teacher is not None:
            pidx, pteach = self.prev_teacher
            x_prev = self.x_pub[jnp.asarray(pidx)]
            new_params = self._distill_all(new_params, x_prev, pteach, part_j)
        upd = self._local_train_all(new_params, t)
        self.client_params = _select_cohorts(upd, new_params, part_c)

        # --- request list (cache) ----------------------------------------
        if self.use_cache:
            miss = cache_lib.miss_mask(
                self.cache_g, idx_j, t, self.D,
                probabilistic=self.probabilistic_expiry,
                key=jax.random.fold_in(jax.random.PRNGKey(c.seed), t)
                if self.probabilistic_expiry else None)
        else:
            miss = jnp.ones(len(idx), bool)
        n_req = int(jnp.sum(miss))
        # shared delta-coding base: the synchronized cache at P^t (pre-update)
        base, base_present = cache_lib.cached_at(self.cache_g, idx_j)

        # --- uplink: soft-labels on requested samples ---------------------
        # predict_soft collapses the cohort axis: soft-label shapes are
        # architecture-independent, so everything from here down (wire
        # codecs, strategy aggregation, cache, ledger) sees one (K, m, N)
        # stack in global client order regardless of the cohort mix.
        x_round = self.x_pub[idx_j]
        z_all = self._predict_all(self.client_params, x_round)  # (K, m, N)
        # jax mode matches the device engines' per-round transmit key;
        # numpy mode has no key stream (strategies must tolerate None)
        tkey = (jax.random.fold_in(jax.random.fold_in(self._key_rounds, t),
                                   strat_base.TRANSMIT_SALT)
                if self.rng_backend == "jax" else None)
        z_all = s.transmit(z_all, tkey)
        z_tx = z_all  # as transmitted (pre uplink codec): telemetry's
        # reference for the codec quantization-error gauge
        if not self.codec_up.is_identity:  # lossy wire: what the server sees
            z_all = self.codec_up.roundtrip(z_all, base=base,
                                            present=base_present)
        um = s.upload_mask(z_all)
        # only participating clients contribute
        zsel = z_all[part_j] if n_part < K else z_all
        umsel = None if um is None else (um[part_j] if n_part < K else um)

        fresh, per_client = s.aggregate(zsel, umsel, t)
        if not self.codec_down.is_identity:
            # clients receive (and cache) the decoded broadcast; the server
            # uses the same decoded teacher so both caches stay bit-identical
            fresh = self.codec_down.roundtrip(fresh, base=base,
                                              present=base_present)
            if per_client is not None:
                per_client = self.codec_down.roundtrip(
                    per_client, base=base, present=base_present)

        # --- assemble teacher + cache update ------------------------------
        cache_prev = self.cache_g  # pre-round state: catch-up covers <= t-1
        signals = None
        if self.use_cache:
            teacher = cache_lib.assemble_teacher(self.cache_g, idx_j, fresh, miss)
            self.cache_g, signals = cache_lib.update_global_cache(
                self.cache_g, idx_j, teacher, miss, t)
        else:
            teacher = fresh

        # --- server distillation ------------------------------------------
        self.server_params = distill(self.server_params, x_round, teacher,
                                     c.lr_dist, c.distill_steps)
        # App.-D proxy teacher on the public validation split: the clients'
        # (server-visible) aggregated predictions on held-out public data
        zv = self._predict_all(self.client_params, self.x_pub[self.pub_val_idx])
        self.last_teacher_val = jnp.mean(zv, axis=0)
        if per_client is not None:  # COMET: personalized teachers
            if per_client.shape[0] != K:  # partial participation: clients
                # without a cluster this round fall back to the global teacher
                fallback = jnp.broadcast_to(teacher, (K,) + teacher.shape)
                per_client = fallback.at[jnp.asarray(np.nonzero(part)[0])].set(per_client)
            teach_next = per_client
        else:
            teach_next = teacher
        self.prev_teacher = (idx, teach_next)

        # --- catch-up packages for returning stragglers --------------------
        catch_up = 0.0
        catch_up_pkgs = {}
        if self.use_cache:
            for k in np.nonzero(part)[0]:
                if self.last_sync[k] < t - 1:
                    pkg = cache_lib.make_catch_up(cache_prev, int(self.last_sync[k]))
                    catch_up_pkgs[k] = pkg
                    catch_up += cache_lib.catch_up_bytes(pkg)

        # --- mirrored local caches (verification mode) ---------------------
        if self.track_local_caches and self.use_cache:
            miss_np = np.asarray(miss)
            queue = cache_lib.pack_queue(teacher, miss_np)
            dense = cache_lib.unpack_queue(queue, miss, c.n_classes)
            for k in np.nonzero(part)[0]:
                ck = self.local_caches[k]
                if k in catch_up_pkgs:  # returning straggler
                    ck = cache_lib.apply_catch_up(ck, catch_up_pkgs[k])
                ck, _ = cache_lib.update_local_cache(ck, idx_j, signals, dense, t)
                self.local_caches[k] = ck

        # --- communication accounting --------------------------------------
        # Selective-FD: the confidence filter masks only the *uplink* —
        # each client withholds its unconfident entries among the
        # requested samples — while the server still broadcasts
        # aggregated labels for every requested sample, so the downlink
        # count stays at n_req.  Uplink is exact (possibly fractional
        # per-client average), not a rounded whole-mask fraction.
        uploaded_up = float(n_req)
        if umsel is not None:
            miss_f = jnp.asarray(miss, jnp.float32)
            uploaded_total = float(jnp.sum(
                umsel.astype(jnp.float32) * miss_f[None, :]))
            uploaded_up = uploaded_total / max(n_part, 1)
        cost = comm_lib.distillation_round_cost(
            n_clients=n_part,
            n_selected=len(idx),
            n_up_samples=uploaded_up,
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
        hist.ledger.record(cost)
        if self._telemetry:
            hist.telemetry.append(self._telemetry_row(
                t=t, part_full=part_j, miss=miss, base_present=base_present,
                z_tx=z_tx, z_srv=z_all, fresh=fresh,
                last_sync=jnp.asarray(self.last_sync, jnp.int32),
                uplink=cost.uplink, downlink=cost.downlink,
                catch_up=catch_up))
        self.last_sync[part] = t

    # ------------------------------------------------------------------
    def _eval(self, t: int, hist: History) -> None:
        sa = float(accuracy(self.server_params, self.x_test, self.y_test,
                            jnp.ones(len(self.y_test))))
        accs = [accuracy_v(p, self.xts_c[i], self.yts_c[i],
                           self.tmask_c[i].astype(jnp.float32))
                for i, p in enumerate(self.client_params)]
        ca = float(jnp.mean(self.models.concat(accs)))
        hist.rounds.append(t)
        hist.server_acc.append(sa)
        hist.client_acc.append(ca)
        hist.cohort_client_acc.append([float(jnp.mean(a)) for a in accs])
        hist.cumulative_mb.append(hist.ledger.cumulative_total / 1e6)
        # Appendix-D proxies (computable in deployment without test labels)
        if self.last_teacher_val is not None:
            hist.server_val_loss.append(float(val_loss_soft(
                self.server_params, self.x_pub[self.pub_val_idx],
                self.last_teacher_val)))
        hist.client_val_loss.append(float(jnp.mean(self.models.concat(
            [val_loss_hard_v(p, self.xs_c[i], self.ys_c[i],
                             self.val_mask_c[i].astype(jnp.float32))
             for i, p in enumerate(self.client_params)]))))
