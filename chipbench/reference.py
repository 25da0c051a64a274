"""Plain reference of the federated round: SCARLET with the synchronized
soft-label cache, in straightforward ``jax.numpy``.

It imports nothing of the system under test and takes nothing it made:
data, partition, initial weights, participation and public-subset draws
are all rebuilt here from the seed, following the configuration.  The
client model, the inputs and the partition are the files the
configuration names (``chipbench.spec.parts``); the round below knows no
model.  Per round ``t`` (SCARLET Alg. 1 with the Alg.-3 expiry test):

1. the key ``fold_in(fold_in(PRNGKey(seed), 43), t)`` splits into the
   public-subset key (``|P^t|`` of ``|P|``, sorted) and the
   participation key (``m`` of ``K`` without replacement, or everyone);
2. participants distill on the previous round's teacher, then train
   locally on their private rows (plain SGD, full batch);
3. an entry of ``P^t`` is requested when it is absent from the cache or
   older than ``D`` rounds;
4. participants predict soft labels on ``P^t`` and send them through the
   ``cache_delta+quant<b>`` wire round trip: the residual against the
   cached entry (uniform prior where none), last class dropped, per-row
   min-max quantization, reconstruction onto the simplex;
5. the server averages the participants' labels and sharpens them
   (Enhanced ERA, ``z**beta / sum z**beta``), serves cached entries where
   they are fresh, updates the cache, and distills its own model;
6. the ledger charges the uplink payload, the downlink labels, request
   list and cache signals, and catch-up packages for returning clients.

``dtype="float32"`` runs with every matmul at ``highest`` precision;
``dtype="bfloat16"`` is the lower-precision control: parameters, data and
arithmetic in bfloat16.  Inputs that are not floating point, such as
token ids, keep their own dtype.
"""
from __future__ import annotations

import dataclasses
import functools
from pathlib import Path
from typing import Any, Dict, List

import jax
import jax.numpy as jnp
import numpy as np

from chipbench import spec
from chipbench.compare import leaf_change

NEVER = -(2 ** 30)
EVAL_CHUNK = 512  # clients per evaluation block


@dataclasses.dataclass(frozen=True)
class Setting:
    """What one reference run needs, from a configuration and a mix.  The
    configuration's family, inputs and partition read their own keys
    from ``config``."""
    config: Dict[str, Any]
    n_clients: int
    n_classes: int
    public_size: int
    public_per_round: int
    private_size: int
    local_steps: int
    distill_steps: int
    lr: float
    lr_dist: float
    beta: float
    cache_duration: int
    quant_bits: int
    index_bytes: float
    participants: int  # m; equal to n_clients for full participation
    eval_every: int


@dataclasses.dataclass
class Result:
    """The first ``rounds`` rounds of one federation, as the reference ran them."""
    uplink: List[float]
    downlink: List[float]
    evals: Dict[int, Dict[str, float]]
    server_change: Dict[str, float]   # leaf -> ||theta_R - theta_0||
    client_change: Dict[str, float]   # leaf -> same, over the client stack
    cache_values: np.ndarray
    cache_ts: np.ndarray
    cache_present: np.ndarray


def _cast(a: np.ndarray, dt):
    """Floating-point inputs in the run's dtype; others as they are."""
    return jnp.asarray(a, dt) if np.issubdtype(a.dtype, np.floating) else jnp.asarray(a)


def _ce(logits, p, x, y, mask):
    logp = jax.nn.log_softmax(logits(p, x), axis=-1)
    nll = -jnp.take_along_axis(logp, y[:, None], axis=-1)[:, 0]
    return jnp.sum(nll * mask) / jnp.maximum(jnp.sum(mask), 1.0)


def _kl(logits, p, x, teacher):
    logp = jax.nn.log_softmax(logits(p, x), axis=-1)
    t = jnp.clip(teacher, 1e-12, 1.0)
    return jnp.mean(jnp.sum(t * (jnp.log(t) - logp), axis=-1))


def _sgd(loss, p, args, lr, steps):
    for _ in range(steps):
        g = jax.grad(loss)(p, *args)
        p = jax.tree_util.tree_map(lambda a, b: a - lr * b, p, g)
    return p


def _wire(z, base, present, levels):
    """cache_delta+quant round trip of one client's (m, N) labels."""
    n = z.shape[-1]
    b = jnp.where(present[:, None], base, 1.0 / n).astype(z.dtype)
    r = (z - b)[:, :-1]
    rmin = jnp.min(r, axis=-1, keepdims=True)
    rmax = jnp.max(r, axis=-1, keepdims=True)
    scale = jnp.maximum(rmax - rmin, 1e-9)
    q = jnp.clip(jnp.round((r - rmin) / scale * levels) / levels, 0.0, 1.0)
    r = q * scale + rmin
    r = jnp.concatenate([r, -jnp.sum(r, axis=-1, keepdims=True)], axis=-1)
    zz = jnp.maximum(b + r, 0.0)
    return zz / jnp.maximum(jnp.sum(zz, axis=-1, keepdims=True), 1e-9)


def _sharpen(zbar, beta):
    return jax.nn.softmax(beta * jnp.log(jnp.maximum(zbar, 1e-12)), axis=-1)


class Reference:
    """One federation from one seed; ``run(rounds)`` plays its first rounds."""

    def __init__(self, s: Setting, seed: int, dtype: str = "float32",
                 root: Path = spec.REPO):
        self.s, self.seed = s, seed
        self.dtype = jnp.dtype(dtype)
        dt = self.dtype
        parts = spec.parts(s.config, root)
        family, shards = parts["family"], parts["partition"].shards
        self.logits = family.logits
        self._ce = functools.partial(_ce, family.logits)
        self._kl = functools.partial(_kl, family.logits)
        d = parts["inputs"].make(s.config, seed)
        xs, ys, valid = shards(d["x_private"], d["y_private"], s.n_clients)
        xts, yts, tvalid = shards(d["x_test"], d["y_test"], s.n_clients)
        n_valid = valid.sum(1).astype(np.float32)
        cut = np.maximum((n_valid * np.float32(0.9)).astype(np.int32), 1)
        pos = np.arange(valid.shape[1])[None, :]
        self.xs = _cast(xs, dt)
        self.ys = jnp.asarray(ys)
        self.train_mask = jnp.asarray(valid & (pos < cut[:, None]), dt)
        self.val_mask = jnp.asarray(valid & (pos >= cut[:, None]), dt)
        self.xts, self.yts = _cast(xts, dt), jnp.asarray(yts)
        self.tmask = jnp.asarray(tvalid, dt)
        self.x_pub = _cast(d["x_public"], dt)
        self.x_test, self.y_test = _cast(d["x_test"], dt), jnp.asarray(d["y_test"])
        n_val = max(s.public_size // 10, 10)
        self.pub_val_idx = jnp.asarray(np.random.default_rng(seed + 99).choice(
            s.public_size, n_val, replace=False))
        del d, xs, xts

        keys = jax.random.split(jax.random.PRNGKey(seed), s.n_clients + 1)
        self.clients = jax.vmap(lambda k: family.init(k, s.config, dt))(keys[:-1])
        self.server = family.init(keys[-1], s.config, dt)
        self.clients0 = jax.tree_util.tree_map(jnp.copy, self.clients)
        self.server0 = self.server
        self.values = jnp.zeros((s.public_size, s.n_classes), dt)
        self.ts = jnp.full((s.public_size,), NEVER, jnp.int32)
        self.present = jnp.zeros((s.public_size,), bool)
        self.prev = None  # (idx, teacher) of the last round
        self.last_sync = np.zeros(s.n_clients, np.int64)
        self.key_rounds = jax.random.fold_in(jax.random.PRNGKey(seed), 43)
        self._round = jax.jit(self._round_fn, static_argnames=("have_prev",),
                              donate_argnums=(0,))
        self._eval_chunk = jax.jit(self._eval_chunk_fn)

    # -- one round, participants only -----------------------------------
    def _draws(self, t):
        s = self.s
        k_idx, k_part = jax.random.split(jax.random.fold_in(self.key_rounds, t))
        idx = jnp.sort(jax.random.choice(k_idx, s.public_size,
                                         (s.public_per_round,), replace=False))
        if s.participants >= s.n_clients:
            sel = jnp.arange(s.n_clients)
        else:
            sel = jax.random.choice(k_part, s.n_clients, (s.participants,),
                                    replace=False)
        return idx, sel

    def _round_fn(self, clients, server, cache, prev, t, idx, sel, xs, ys,
                  train_mask, x_pub, *, have_prev):
        s, dt = self.s, self.dtype
        values, ts, present = cache
        lr, lr_dist = jnp.asarray(s.lr, dt), jnp.asarray(s.lr_dist, dt)
        p = jax.tree_util.tree_map(lambda a: a[sel], clients)
        if have_prev:
            x_prev = x_pub[prev[0]]
            p = jax.vmap(lambda q: _sgd(self._kl, q, (x_prev, prev[1]), lr_dist,
                                        s.distill_steps))(p)
        p = jax.vmap(lambda q, x, y, m: _sgd(self._ce, q, (x, y, m), lr, s.local_steps))(
            p, xs[sel], ys[sel], train_mask[sel])
        clients = jax.tree_util.tree_map(lambda a, b: a.at[sel].set(b), clients, p)

        fresh_ok = jnp.logical_and(present[idx], t - ts[idx] <= s.cache_duration)
        miss = jnp.logical_not(fresh_ok)
        base, base_present = values[idx], present[idx]
        x_round = x_pub[idx]
        z = jax.vmap(lambda q: jax.nn.softmax(self.logits(q, x_round), axis=-1))(p)
        levels = jnp.asarray(2 ** s.quant_bits - 1, dt)
        z = jax.vmap(lambda zk: _wire(zk, base, base_present, levels))(z)
        fresh = _sharpen(jnp.sum(z, axis=0) / jnp.asarray(z.shape[0], dt),
                         jnp.asarray(s.beta, dt))
        teacher = jnp.where(miss[:, None], fresh, base)
        values = values.at[idx].set(teacher)
        ts = ts.at[idx].set(jnp.where(miss, t, ts[idx]))
        present = present.at[idx].set(True)
        server = _sgd(self._kl, server, (x_round, teacher), lr_dist, s.distill_steps)
        return clients, server, (values, ts, present), teacher, jnp.sum(miss)

    def _eval_chunk_fn(self, p, xts, yts, tmask, xs, ys, vmask, x_val):
        def one(q, xt, yt, tm, x, y, vm):
            ok = (jnp.argmax(self.logits(q, xt), axis=-1) == yt).astype(jnp.float32)
            acc = jnp.sum(ok * tm.astype(jnp.float32)) / jnp.maximum(
                jnp.sum(tm.astype(jnp.float32)), 1.0)
            zv = jax.nn.softmax(self.logits(q, x_val), axis=-1)
            return acc, self._ce(q, x, y, vm).astype(jnp.float32), zv
        acc, vl, zv = jax.vmap(one)(p, xts, yts, tmask, xs, ys, vmask)
        return jnp.sum(acc), jnp.sum(vl), jnp.sum(zv.astype(jnp.float32), axis=0)

    def _evaluate(self) -> Dict[str, float]:
        s, dt = self.s, self.dtype
        x_val = self.x_pub[self.pub_val_idx]
        acc = vl = 0.0
        zsum = None
        for lo in range(0, s.n_clients, EVAL_CHUNK):
            hi = min(lo + EVAL_CHUNK, s.n_clients)
            p = jax.tree_util.tree_map(lambda a: a[lo:hi], self.clients)
            a, v, z = self._eval_chunk(p, self.xts[lo:hi], self.yts[lo:hi],
                                       self.tmask[lo:hi], self.xs[lo:hi],
                                       self.ys[lo:hi], self.val_mask[lo:hi], x_val)
            acc, vl = acc + float(a), vl + float(v)
            zsum = z if zsum is None else zsum + z
        teacher_val = (zsum / s.n_clients).astype(dt)
        ok = jnp.argmax(self.logits(self.server, self.x_test), axis=-1) == self.y_test
        return {"server_acc": float(jnp.mean(ok)),
                "client_acc": acc / s.n_clients,
                "server_val_loss": float(self._kl(self.server, x_val, teacher_val)),
                "client_val_loss": vl / s.n_clients}

    def run(self, rounds: int) -> Result:
        s = self.s
        up, down, evals = [], [], {}
        per_entry = s.n_classes * 4.0 + 8.0
        ib = s.index_bytes
        with jax.default_matmul_precision(
                "highest" if self.dtype == jnp.float32 else "default"):
            for t in range(1, rounds + 1):
                idx, sel = self._draws(t)
                sel_np = np.asarray(sel)
                # catch-up: entries newer than a returning client's last sync,
                # counted on the cache as it stood before this round
                ts_np, pres_np = np.asarray(self.ts), np.asarray(self.present)
                ls = self.last_sync[sel_np]
                newer = np.sort(np.where(pres_np, ts_np, NEVER - 1))
                counts = len(newer) - np.searchsorted(newer, ls, side="right")
                catch_up = float(np.sum(np.where(ls < t - 1, counts, 0)) * per_entry)
                have_prev = self.prev is not None
                out = self._round(
                    self.clients, self.server, (self.values, self.ts, self.present),
                    self.prev if have_prev else None, jnp.int32(t), idx, sel,
                    self.xs, self.ys, self.train_mask, self.x_pub, have_prev=have_prev)
                self.clients, self.server, cache, teacher, n_req = out
                self.values, self.ts, self.present = cache
                self.prev = (idx, teacher)
                n, n_req = len(sel_np), int(n_req)
                up.append(n * n_req * (s.n_classes - 1) * s.quant_bits / 8.0)
                down.append(n * (n_req * s.n_classes * 4.0 + n_req * ib
                                 + s.public_per_round * ib
                                 + s.public_per_round * 0.25) + catch_up)
                self.last_sync[sel_np] = t
                if t % s.eval_every == 0 or t == rounds:
                    evals[t] = self._evaluate()
        return Result(
            uplink=up, downlink=down, evals=evals,
            server_change=leaf_change(self.server, self.server0),
            client_change=leaf_change(self.clients, self.clients0),
            cache_values=np.asarray(self.values, np.float32),
            cache_ts=np.asarray(self.ts), cache_present=np.asarray(self.present))
