"""Client scenarios: orthogonal behaviors composable onto any strategy.

A :class:`Scenario` answers, for each round ``t``: which clients
participate, and with what local-training schedule.  It is deliberately
orthogonal to the :class:`~repro.fl.strategies.Strategy` axis (how
soft-labels are aggregated): any scenario runs against any strategy, so
a participation/straggler sweep over all six methods is a plain product
of the two registries.

Three orthogonal knobs:

- **Participation** — per-round client sampling: ``full`` (everyone),
  ``fraction`` (exactly ``max(round(rate*K), 1)`` clients, the paper's
  partial-participation model), or ``bernoulli`` (each client joins
  independently with probability ``rate``, so the per-round cohort size
  itself is random).
- **Outages** — deterministic offline windows per client (dropouts /
  stragglers).  A client inside an outage window never participates;
  when the window ends and it is sampled again, the engine sends it a
  cache catch-up package (Section III-D), which is exactly the path
  these masks exist to exercise.
- **Heterogeneity** — per-client local-step counts and learning-rate
  scales (plus an optional global per-round lr decay).  The engine
  keeps the client axis fully vmapped: heterogeneous schedules run as
  one jitted program over stacked params with per-client step masks,
  not as a Python loop over clients.

Sampling uses a dedicated numpy Generator owned by the engine (separate
from the public-subset stream), so two runs that differ only in their
scenario still select identical public subsets ``P^t`` — that is what
makes communication ledgers comparable across scenarios, and what the
"partial never exceeds full uplink" property test relies on.
"""
from __future__ import annotations

from dataclasses import dataclass, field
from typing import Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np

__all__ = [
    "Participation",
    "Outage",
    "Heterogeneity",
    "Scenario",
    "full_participation",
    "fixed_fraction",
    "bernoulli_participation",
]


@dataclass(frozen=True)
class Participation:
    """Per-round client-sampling policy.

    kind:
      ``full``       every client, every round (no RNG consumed).
      ``fraction``   exactly ``max(round(rate*K), 1)`` clients, sampled
                     uniformly without replacement (paper Alg. 1).
      ``bernoulli``  each client independently with probability ``rate``.
    """

    kind: str = "full"
    rate: float = 1.0

    def max_participants(self, n_clients: int) -> int:
        """The most clients one draw can hold: the fixed count of
        ``fraction``, every client otherwise."""
        if self.kind == "fraction":
            return min(max(int(round(self.rate * n_clients)), 1), n_clients)
        if self.kind in ("full", "bernoulli"):
            return n_clients
        raise ValueError(f"unknown participation kind: {self.kind!r}")

    def sample(self, n_clients: int, rng: np.random.Generator) -> np.ndarray:
        if self.kind == "full":
            return np.ones(n_clients, bool)
        if self.kind == "fraction":
            n = self.max_participants(n_clients)
            mask = np.zeros(n_clients, bool)
            mask[rng.choice(n_clients, n, replace=False)] = True
            return mask
        if self.kind == "bernoulli":
            return rng.random(n_clients) < self.rate
        raise ValueError(f"unknown participation kind: {self.kind!r}")

    def sample_device(self, key: jnp.ndarray, n_clients: int) -> jnp.ndarray:
        """jit/scan-safe twin of :meth:`sample` on a jax PRNG key.

        Same policy semantics, different (jax) RNG stream: runs with
        ``rng_backend="jax"`` draw from this stream both in the host
        loop and inside the scanned engine, which is what makes the two
        engines bit-comparable.
        """
        if self.kind == "full":
            return jnp.ones(n_clients, bool)
        if self.kind == "fraction":
            n = self.max_participants(n_clients)
            sel = jax.random.choice(key, n_clients, (n,), replace=False)
            return jnp.zeros(n_clients, bool).at[sel].set(True)
        if self.kind == "bernoulli":
            return jax.random.uniform(key, (n_clients,)) < self.rate
        raise ValueError(f"unknown participation kind: {self.kind!r}")


def full_participation() -> "Participation":
    return Participation("full")


def fixed_fraction(rate: float) -> "Participation":
    return Participation("fraction", rate)


def bernoulli_participation(rate: float) -> "Participation":
    return Participation("bernoulli", rate)


@dataclass(frozen=True)
class Outage:
    """Client ``client`` is offline for rounds ``start..end`` (1-based,
    inclusive).  Overrides any participation draw for those rounds."""

    client: int
    start: int
    end: int

    def covers(self, t: int) -> bool:
        return self.start <= t <= self.end


@dataclass(frozen=True)
class Heterogeneity:
    """Per-client local-training schedules.

    ``local_steps[k]``: client k's local epoch count E_k (defaults to the
    config's homogeneous ``local_steps``).  ``lr_scale[k]`` multiplies
    the config lr for client k.  ``lr_decay`` applies a global
    ``decay**(t-1)`` factor each round.  Any field left ``None`` falls
    back to the homogeneous config value.
    """

    local_steps: Optional[Tuple[int, ...]] = None
    lr_scale: Optional[Tuple[float, ...]] = None
    lr_decay: float = 1.0

    def resolve(self, n_clients: int, base_lr: float,
                base_steps: int) -> Tuple[np.ndarray, np.ndarray, int]:
        """-> (lr_k (K,), steps_k (K,), max_steps)."""
        steps = (np.full(n_clients, base_steps, np.int32)
                 if self.local_steps is None
                 else np.asarray(self.local_steps, np.int32))
        scale = (np.ones(n_clients, np.float32)
                 if self.lr_scale is None
                 else np.asarray(self.lr_scale, np.float32))
        if steps.shape != (n_clients,) or scale.shape != (n_clients,):
            raise ValueError("heterogeneity schedules must have one entry "
                             f"per client ({n_clients})")
        return base_lr * scale, steps, int(steps.max())


@dataclass(frozen=True)
class Scenario:
    """Composition of participation sampling, outage windows, and
    per-client schedule heterogeneity.

    ``min_participants`` guards aggregation: if a round's draw comes up
    empty while some client is *available* (not in an outage window),
    the lowest-indexed available clients are conscripted.  If every
    client is offline the round proceeds with zero participants — the
    engine skips client updates and uplink but the cache keeps aging.
    """

    participation: Participation = field(default_factory=Participation)
    outages: Tuple[Outage, ...] = ()
    heterogeneity: Optional[Heterogeneity] = None
    min_participants: int = 1

    @classmethod
    def from_participation_rate(cls, rate: float) -> "Scenario":
        """Legacy ``FLConfig.participation`` semantics (Alg. 1)."""
        if rate >= 1.0:
            return cls(participation=full_participation())
        return cls(participation=fixed_fraction(rate))

    def max_participants(self, n_clients: int) -> int:
        """The most clients any round can take: the draw's bound, lifted
        to ``min_participants`` by conscription.  Outage windows (and the
        async engine's unreachable or in-flight clients) only remove
        clients, so they leave the bound as it is."""
        return min(n_clients,
                   max(self.participation.max_participants(n_clients),
                       self.min_participants))

    def offline_mask(self, t: int, n_clients: int) -> np.ndarray:
        off = np.zeros(n_clients, bool)
        for o in self.outages:
            if o.covers(t):
                off[o.client] = True
        return off

    def offline_masks(self, n_rounds: int, n_clients: int,
                      start: int = 1) -> np.ndarray:
        """``(T, K)`` stacked offline masks for rounds
        ``start..start+n_rounds-1`` — outage windows are static config,
        so the scanned engines precompute them once and feed them as
        scan inputs (``start > 1`` for checkpoint-resumed runs)."""
        if n_rounds == 0:  # zero-round legs still need a (0, K) scan input
            return np.zeros((0, n_clients), bool)
        return np.stack([self.offline_mask(t, n_clients)
                         for t in range(start, start + n_rounds)])

    def participation_mask_device(self, key: jnp.ndarray,
                                  offline: jnp.ndarray) -> jnp.ndarray:
        """jit/scan-safe twin of :meth:`participation_mask`.

        ``offline`` is this round's ``(K,)`` offline mask (a row of
        :meth:`offline_masks`).  Conscription mirrors the host loop:
        when the draw comes up short, the lowest-indexed available
        clients are added until ``min_participants`` is met (or nobody
        is left).
        """
        n_clients = offline.shape[0]
        mask = self.participation.sample_device(key, n_clients)
        mask = jnp.logical_and(mask, jnp.logical_not(offline))
        deficit = self.min_participants - jnp.sum(mask)
        candidates = jnp.logical_and(jnp.logical_not(mask),
                                     jnp.logical_not(offline))
        rank = jnp.cumsum(candidates)  # 1-based rank among candidates
        conscript = jnp.logical_and(candidates, rank <= deficit)
        return jnp.logical_or(mask, conscript)

    def participation_mask(self, t: int, n_clients: int,
                           rng: np.random.Generator) -> np.ndarray:
        mask = self.participation.sample(n_clients, rng)
        off = self.offline_mask(t, n_clients)
        mask &= ~off
        if mask.sum() < self.min_participants:
            avail = np.nonzero(~off)[0]
            need = self.min_participants - int(mask.sum())
            for k in avail:
                if need <= 0:
                    break
                if not mask[k]:
                    mask[k] = True
                    need -= 1
        return mask
