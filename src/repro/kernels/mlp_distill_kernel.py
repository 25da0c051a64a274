"""Pallas TPU kernel: a client's whole SGD distillation run in VMEM.

Client distillation (``repro.fl.rounds.distill``) takes ``steps`` SGD
steps of an MLP client on the KL loss to a teacher over the same public
rows.  Under ``vmap`` XLA runs each step as a dozen ops over the whole
client stack, and every op writes its float32 activations to HBM for
the next one to read back; the first layer's weight gradient re-reads
and rewrites the whole first-layer weight stack every step.  This
kernel runs one client per grid step instead: its parameters come into
VMEM once, all ``steps`` steps run in one ``fori_loop`` with the
activations and gradients in VMEM, and the parameters go back once, in
place (``input_output_aliases``).  The public rows and a shared teacher
have a constant block index, so they are fetched once per call.

Layout: activations are features x rows (features on sublanes, the
public rows on lanes, padded to the 128-lane tile), so a hidden width of
200 is never padded to 256 lanes.  Weights keep the program's
``(fan_in, fan_out)`` layout: the forward pass contracts the weight's
first dim, the weight gradient is ``activations . gradient^T`` over the
rows, and the input gradient is ``W . gradient``.  The biases ride as
the columns of one ``(round_up(max width, 8), n_layers)`` block per
client.  Padded rows carry zero input and a zero teacher, so their
gradient is exactly zero (the teacher is clipped before padding).

Mathematics and precision are ``rounds.distill`` on ``rounds._kl`` as
the chip runs it: ``t = clip(teacher, 1e-12, 1)``, the logits' gradient
``(softmax(l) * sum(t) - t) / m``, relu'(0) = 0, bias gradients are row
sums, ``p -= lr * g``.  Each matmul rounds its two operands to bfloat16
and accumulates in float32 (XLA's DEFAULT precision for float32 dots on
TPU); weights, updates, softmax and masks stay float32.
"""
from __future__ import annotations

import functools
from typing import Dict, Sequence, Tuple

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.kernels.runtime import (
    LANES,
    SUBLANES_F32,
    VMEM_LIMIT_NATIVE,
    default_interpret,
    resolve_interpret,
    sublanes_for_dtype,
)

_EPS_T = 1e-12  # rounds._kl's teacher clip

# The kernel path is taken when a client's working set (below) fits this
# budget; Mosaic is given the working set plus headroom as its scoped
# VMEM limit, above XLA's 16 MiB default (a v5e core has 128 MiB).  The
# working set is an upper estimate: at the 2NN's widths on 1,000 rows it
# reads 19.5 MiB, where a described-v5e compile needs a limit of 16.1.
VMEM_BUDGET = VMEM_LIMIT_NATIVE * 3 // 2
_VMEM_HEADROOM = 4 * 2 ** 20


def _round_up(n: int, k: int) -> int:
    return -(-n // k) * k


def mlp_widths(params: Dict[str, jnp.ndarray]) -> Tuple[int, ...]:
    """``(dim, hidden..., n_classes)`` of a stacked MLP param dict
    (``w0..w{n-1}``, ``b0..b{n-1}``, leading client axis)."""
    n = sum(1 for k in params if k.startswith("w"))
    return (params["w0"].shape[-2],) + tuple(
        params[f"w{i}"].shape[-1] for i in range(n))


def _tile_bytes(rows: int, cols: int, dtype) -> int:
    """VMEM bytes of a ``(rows, cols)`` array padded to its native tile."""
    dt = jnp.dtype(dtype)
    return (_round_up(rows, sublanes_for_dtype(dt)) * _round_up(cols, LANES)
            * dt.itemsize)


def working_set_bytes(widths: Sequence[int], m: int) -> int:
    """VMEM one client's run holds, estimated from above: the
    double-buffered blocks (public rows, teacher, parameters in and out)
    and the live values of a step (each layer's pre-activation in f32
    and activation in bf16, two f32 and one bf16 array as wide as the
    widest layer for the gradients, the weight gradients and the bf16
    weights)."""
    f32, bf16 = jnp.float32, jnp.bfloat16
    mp = _round_up(m, LANES)
    layers = list(zip(widths[:-1], widths[1:]))
    params = sum(_tile_bytes(a, c, f32) for a, c in layers)
    params += _tile_bytes(max(widths[1:]), len(layers), f32)
    blocks = 2 * (_tile_bytes(widths[0], mp, bf16)
                  + _tile_bytes(widths[-1], mp, f32) + 2 * params)
    acts = sum(_tile_bytes(c, mp, f32) + _tile_bytes(c, mp, bf16)
               for c in widths[1:])
    widest = max(widths)
    grads = (2 * _tile_bytes(widest, mp, f32) + _tile_bytes(widest, mp, bf16)
             + sum(_tile_bytes(a, c, f32) + _tile_bytes(a, c, bf16)
                   for a, c in layers))
    return blocks + acts + grads


def use_kernel(params: Dict[str, jnp.ndarray], m: int) -> bool:
    """Dispatch predicate of the engines' client distillation, from the
    backend and shapes alone: the kernel runs where Pallas compiles
    natively (TPU), the public rows' bfloat16 block has ``dim`` on
    sublanes of 16, layer 0's fan-out and every other weight's fan-in
    are sublane multiples, and one client's working set fits
    ``VMEM_BUDGET``.  Everywhere else the XLA path runs."""
    widths = mlp_widths(params)
    return (not default_interpret()
            and widths[0] % sublanes_for_dtype(jnp.bfloat16) == 0
            and widths[1] % SUBLANES_F32 == 0
            and all(a % SUBLANES_F32 == 0 for a in widths[1:-1])
            and working_set_bytes(widths, m) <= VMEM_BUDGET)


def _distill_kernel(keep_ref, x_ref, t_ref, *refs, widths, steps, lr, m):
    n = len(widths) - 1
    w_in, b_in = refs[:n], refs[n]
    w_out, b_out = refs[n + 1:2 * n + 1], refs[2 * n + 1]
    for wi, wo in zip(w_in, w_out):
        wo[...] = wi[...]
    b_out[...] = b_in[...]

    @pl.when(keep_ref[pl.program_id(0)] != 0)
    def _():
        f32, bf16 = jnp.float32, jnp.bfloat16
        x = x_ref[...]                                   # (dim, mp) bf16
        t = t_ref[:widths[-1], :]                        # (N, mp)
        tsum = jnp.sum(t, axis=0, keepdims=True)         # (1, mp)
        tn = (((0,), (0,)), ((), ()))  # W^T . act: contract fan-in
        nt = (((1,), (1,)), ((), ()))  # act . grad^T: contract rows

        def step(_, carry):
            wb = [w_out[i][...].astype(bf16) for i in range(n)]
            acts, pre = [x], []
            for i in range(n):
                if i == 0:  # W0^T . x
                    h = jnp.dot(wb[0], x, preferred_element_type=f32)
                else:
                    h = jax.lax.dot_general(wb[i], acts[i], tn,
                                            preferred_element_type=f32)
                h = h + b_out[:widths[i + 1], i:i + 1]
                if i < n - 1:
                    pre.append(h)
                    acts.append(jnp.maximum(h, 0.0).astype(bf16))
            e = jnp.exp(h - jnp.max(h, axis=0, keepdims=True))
            p = e / jnp.sum(e, axis=0, keepdims=True)
            g = (p * tsum - t) / m                       # (N, mp)
            for i in reversed(range(n)):
                gb = g.astype(bf16)
                if i == 0:  # dW0^T = g . x^T
                    dw = jax.lax.dot_general(gb, x, nt,
                                             preferred_element_type=f32)
                else:
                    dw = jax.lax.dot_general(acts[i], gb, nt,
                                             preferred_element_type=f32)
                db = jnp.sum(g, axis=1, keepdims=True)
                if i > 0:
                    da = jnp.dot(wb[i], gb, preferred_element_type=f32)
                    g = jnp.where(pre[i - 1] > 0, da, 0.0)
                w_out[i][...] -= lr * dw
                b_out[:widths[i + 1], i:i + 1] -= lr * db
            return carry

        jax.lax.fori_loop(0, steps, step, 0)


@functools.partial(jax.jit, static_argnames=("lr", "steps", "interpret"))
def mlp_distill(params: Dict[str, jnp.ndarray], x: jnp.ndarray,
                teacher: jnp.ndarray, keep: jnp.ndarray, *, lr: float,
                steps: int, interpret: bool | None = None):
    """``steps`` SGD distillation steps of every kept client.

    ``params``: stacked MLP params (``(K, fan_in, fan_out)`` weights,
    ``(K, fan_out)`` biases); ``x``: the shared ``(m, dim)`` public rows;
    ``teacher``: a shared ``(m, N)`` or per-client ``(K, m, N)`` soft
    label; ``keep``: ``(K,)`` bool, clients left out return their params
    unchanged.  Returns the updated param dict (in place where the
    caller's buffers are donated).  ``interpret=None`` auto-detects the
    backend.
    """
    interpret = resolve_interpret(interpret)
    widths = mlp_widths(params)
    n = len(widths) - 1
    K = params["w0"].shape[0]
    m, n_cls = x.shape[0], widths[-1]
    mp = _round_up(m, LANES)
    np8 = _round_up(n_cls, SUBLANES_F32)
    cb = _round_up(max(widths[1:]), SUBLANES_F32)

    xt = jnp.pad(x.astype(jnp.bfloat16).T, ((0, 0), (0, mp - m)))
    t = jnp.clip(teacher.astype(jnp.float32), _EPS_T, 1.0)
    t = jnp.pad(jnp.swapaxes(t, -1, -2),
                ((0, 0),) * (t.ndim - 2) + ((0, np8 - n_cls), (0, mp - m)))
    b = jnp.stack([jnp.pad(params[f"b{i}"], ((0, 0), (0, cb - widths[i + 1])))
                   for i in range(n)], axis=-1)          # (K, cb, n)
    # layer 0's weight goes in and out as W0^T: the program keeps the
    # (K, dim, hidden) stack with dim minor (XLA's TPU layout for it),
    # so the swap is a bitcast, and the kernel needs no transpose of it
    ws = [jnp.swapaxes(params["w0"], 1, 2)] + [params[f"w{i}"]
                                               for i in range(1, n)]

    def client(shape):
        return pl.BlockSpec((None,) + shape, lambda k, keep: (k, 0, 0))

    if t.ndim == 3:
        t_spec = client((np8, mp))
    else:
        t_spec = pl.BlockSpec((np8, mp), lambda k, keep: (0, 0))
    w_specs = [client(w.shape[1:]) for w in ws]
    b_spec = client((cb, n))
    vmem = working_set_bytes(widths, m) + _VMEM_HEADROOM
    *out_w, out_b = pl.pallas_call(
        functools.partial(_distill_kernel, widths=widths, steps=steps,
                          lr=lr, m=m),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=1,
            grid=(K,),
            in_specs=[pl.BlockSpec((widths[0], mp), lambda k, keep: (0, 0)),
                      t_spec, *w_specs, b_spec],
            out_specs=[*w_specs, b_spec],
        ),
        out_shape=[*(jax.ShapeDtypeStruct(w.shape, w.dtype) for w in ws),
                   jax.ShapeDtypeStruct(b.shape, b.dtype)],
        # operand 0 is the keep mask, 1 the rows, 2 the teacher
        input_output_aliases={3 + i: i for i in range(n + 1)},
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary",), vmem_limit_bytes=vmem),
        interpret=interpret,
    )(keep.astype(jnp.int32), xt, t, *ws, b)
    out_w[0] = jnp.swapaxes(out_w[0], 1, 2)
    out = {f"w{i}": w for i, w in enumerate(out_w)}
    out.update({f"b{i}": out_b[:, :widths[i + 1], i] for i in range(n)})
    return out


def analysis_cases():
    """(label, fn, abstract args) triples for the static BlockSpec lint
    (:mod:`repro.analysis.pallas_checks`); traced with
    ``interpret=False``, never executed."""
    S, f32 = jax.ShapeDtypeStruct, jnp.float32

    def case(widths, K, m, per_client):
        params = {}
        for i, (a, c) in enumerate(zip(widths[:-1], widths[1:])):
            params[f"w{i}"] = S((K, a, c), f32)
            params[f"b{i}"] = S((K, c), f32)
        t = S((K, m, widths[-1]) if per_client else (m, widths[-1]), f32)
        return (lambda p, x, t, keep: mlp_distill(
                    p, x, t, keep, lr=0.1, steps=5, interpret=False),
                (params, S((m, widths[0]), f32), t, S((K,), jnp.bool_)))

    return [
        ("mlp_distill/2nn-K100-m1000", *case((784, 200, 200, 10), 100, 1000,
                                             False)),
        ("mlp_distill/toy-K8-m24-perclient", *case((16, 16, 4), 8, 24, True)),
    ]
