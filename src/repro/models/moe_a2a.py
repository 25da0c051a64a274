"""Expert-parallel MoE dispatch with a TRUE all-to-all (shard_map).

The §Perf hillclimb showed that pinning the dispatched buffer to an
expert-sharded layout (`MOE_DISPATCH_SPEC`) removes the 16x compute
replication of the TP baseline, but XLA implements the token scatter as
all-gather(tokens)+select (~14 GB/layer/pass on kimi) — collective
became the dominant term.  This module is the next rung: an explicit
``shard_map`` dispatch where each data shard

  1. routes its local tokens (router weights are replicated),
  2. sorts them by destination expert shard (expert e lives on shard
     e // E_loc) into fixed-capacity per-destination send buffers,
  3. exchanges buffers with ``jax.lax.all_to_all`` (bytes moved =
     tokens x D x top_k x overflow factor — NOT the full token tensor),
  4. runs its local experts with the standard capacity dispatch,
  5. all-to-alls results back, unsorts, and combines with gates.

Per-device moved bytes on kimi train drop from ~14 GB/layer/pass
(all-gather) to ~0.9 GB (2 x T_loc·top_k·D·cap_factor / n_shards),
projected collective term 299 s -> ~20 s.

Expert weights must be sharded over the "data" axis on their leading
(expert) dim — the FSDP rule already does this (`experts -> data`).
"""
from __future__ import annotations

import functools
import math
from typing import Tuple

import jax
import jax.numpy as jnp

from repro.models import common as cm


def _local_expert_ffn(xb: jnp.ndarray, w1, w3, w2, model_axis=None) -> jnp.ndarray:
    # w1/w3 carry F/model_size columns and w2 F/model_size rows inside the
    # shard_map body: partial contributions are psum-reduced over "model".
    h = jnp.einsum("ecd,edf->ecf", xb, w1)
    g = jnp.einsum("ecd,edf->ecf", xb, w3)
    y = jnp.einsum("ecf,efd->ecd", jax.nn.silu(h) * g, w2)
    if model_axis is not None:
        y = jax.lax.psum(y, model_axis)
    return y


def moe_ffn_a2a(
    x: jnp.ndarray,        # (B, S, D) — sharded over axis_name on B
    router: jnp.ndarray,   # (D, E)    — replicated
    w1: jnp.ndarray,       # (E, D, F) — experts sharded over axis_name
    w3: jnp.ndarray,
    w2: jnp.ndarray,       # (E, F, D)
    *,
    top_k: int,
    mesh: jax.sharding.Mesh,
    axis_name: str = "data",
    capacity_factor: float = 1.25,
) -> Tuple[jnp.ndarray, jnp.ndarray]:
    """Expert-parallel token-choice MoE with explicit all-to-all.

    Returns (out (B,S,D), aux load-balance loss).  Call under jit with
    ``mesh``; inputs may carry any sharding — shard_map re-partitions.
    """
    from jax.sharding import PartitionSpec as P

    E = router.shape[1]
    F = w1.shape[-1]
    n_shards = mesh.shape[axis_name]
    assert E % n_shards == 0, (E, n_shards)
    e_loc = E // n_shards
    # keep the FFN dim tensor-parallel inside the body when divisible
    model_axis = "model" if ("model" in mesh.axis_names
                             and F % mesh.shape["model"] == 0
                             and mesh.shape["model"] > 1) else None

    def local_fn(xs, router, w1_l, w3_l, w2_l):
        # xs: (B_loc, S, D); w*_l: (E_loc, D, F)
        Bl, S, D = xs.shape
        T = Bl * S
        xt = xs.reshape(T, D)
        logits = jnp.einsum("td,de->te", xt.astype(jnp.float32),
                            router.astype(jnp.float32))
        probs = jax.nn.softmax(logits, axis=-1)
        gate, eidx = jax.lax.top_k(probs, top_k)           # (T, k)
        gate = gate / (jnp.sum(gate, -1, keepdims=True) + 1e-9)
        # aux loss from local stats (psum-averaged)
        assign = jnp.zeros((T, E), jnp.float32).at[
            jnp.arange(T)[:, None], eidx].add(1.0)
        aux = E * jnp.mean(jnp.mean(assign, 0) * jnp.mean(probs, 0))
        aux = jax.lax.pmean(aux, axis_name)

        # ---- single-stage dispatch: sort by GLOBAL expert id -----------
        # Because experts are contiguous per shard (expert e lives on
        # shard e // e_loc), an expert-major send buffer is also
        # shard-major: one sort covers both the inter-shard exchange and
        # the per-expert grouping — after the all-to-all a transpose
        # (not a second sort/scatter chain) feeds the expert matmuls.
        flat_e = eidx.reshape(-1)                          # (T*k,)
        order = jnp.argsort(flat_e)                        # stable
        exp_s = flat_e[order]
        tok_s = order // top_k                             # source token id

        cap_e = int(math.ceil(T * top_k / E * capacity_factor))
        cap_e = max((cap_e + 7) // 8 * 8, 8)
        counts = jnp.bincount(flat_e, length=E)
        starts = jnp.cumsum(counts) - counts
        pos = jnp.arange(T * top_k) - starts[exp_s]
        keep = pos < cap_e
        slot = exp_s * cap_e + jnp.clip(pos, 0, cap_e - 1)
        slot = jnp.where(keep, slot, E * cap_e)            # OOB -> dropped

        send_x = jnp.zeros((E * cap_e, D), xs.dtype
                           ).at[slot].set(xt[tok_s], mode="drop")

        # ---- exchange: (n_shards, e_loc*cap_e, D) split along axis 0 ----
        recv_x = jax.lax.all_to_all(
            send_x.reshape(n_shards, e_loc * cap_e, D),
            axis_name, 0, 0, tiled=False)                  # (src, e_loc*cap_e, D)
        # regroup per local expert: (src, e_loc, cap_e, D) -> (e_loc, src*cap_e, D)
        buf = recv_x.reshape(n_shards, e_loc, cap_e, D) \
                    .transpose(1, 0, 2, 3).reshape(e_loc, n_shards * cap_e, D)
        yb = _local_expert_ffn(buf, w1_l, w3_l, w2_l, model_axis)

        # ---- return path (inverse transpose + all-to-all) ---------------
        back = yb.reshape(e_loc, n_shards, cap_e, D) \
                 .transpose(1, 0, 2, 3).reshape(n_shards, e_loc * cap_e, D)
        y_home = jax.lax.all_to_all(back, axis_name, 0, 0, tiled=False)
        y_flat = y_home.reshape(E * cap_e, D)
        # gather back to sorted token-slots, unsort, gate-combine over k
        y_slot = jnp.where(keep[:, None],
                           y_flat[jnp.clip(slot, 0, E * cap_e - 1)], 0)
        contrib = jnp.zeros((T * top_k, D), xs.dtype).at[order].set(y_slot)
        gate_f = gate.reshape(-1).astype(xs.dtype)
        out = jnp.sum((contrib * gate_f[:, None]).reshape(T, top_k, D), axis=1)
        return out.reshape(Bl, S, D), aux

    w1_spec = P(axis_name, None, model_axis)
    w2_spec = P(axis_name, model_axis, None)
    fn = jax.shard_map(
        local_fn, mesh=mesh,
        in_specs=(P(axis_name), P(), w1_spec, w1_spec, w2_spec),
        out_specs=(P(axis_name), P()),
        check_vma=False,
    )
    return fn(x, router, w1, w3, w2)
