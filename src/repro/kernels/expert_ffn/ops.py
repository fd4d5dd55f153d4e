"""Public op: grouped expert FFN — Pallas kernel on TPU, jnp oracle
elsewhere (or interpret=True for kernel-path testing on CPU).

``counts`` (E,) int32 selects the ragged skip-empty variant: capacity
blocks holding no real tokens skip their MXU work on TPU (pl.when), and
the oracle masks the same rows — empty/skewed workloads cost what they
contain, not E x C.  ``expert_ids`` (G,) additionally maps G row groups
onto the E weight sets (the expert-parallel receive-bucket entry —
models/moe_ep.py).  ``layer`` takes whole (L, E, ...) weight stacks and
uses that layer's, which the kernel reads in place (the scanned resident
MoE layers, models/model.py).

The kernel path is wrapped in a custom VJP — kernel forward, einsum
oracle backward — because ``pallas_call`` has no autodiff rule: without
it any grad through the TPU paths (single-device dense, EP receive-side)
would raise, and both are on train_step's path."""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np

from .kernel import _sublane, expert_ffn as expert_ffn_pallas
from .ref import expert_ffn_ragged_ref, expert_ffn_ref


def _layer_slice(layer, *ws):
    """Layer ``layer``'s (E, ...) weights of (L, E, ...) stacks."""
    lyr = jnp.reshape(layer, ())
    return tuple(w[lyr] for w in ws)


def _oracle(xe, w_gate, w_up, w_down, counts, expert_ids, act, layer=None):
    if layer is not None:
        w_gate, w_up, w_down = _layer_slice(layer, w_gate, w_up, w_down)
    if counts is None:
        return expert_ffn_ref(xe, w_gate, w_up, w_down, act=act)
    return expert_ffn_ragged_ref(xe, w_gate, w_up, w_down, counts,
                                 act=act, expert_ids=expert_ids)


@functools.partial(jax.custom_vjp, nondiff_argnums=(7, 8))
def _kernel_call(xe, w_gate, w_up, w_down, counts, expert_ids, layer,
                 act, interpret):
    return expert_ffn_pallas(xe, w_gate, w_up, w_down, counts=counts,
                             act=act, expert_ids=expert_ids, layer=layer,
                             interpret=interpret)


def _kernel_call_fwd(xe, w_gate, w_up, w_down, counts, expert_ids, layer,
                     act, interpret):
    y = _kernel_call(xe, w_gate, w_up, w_down, counts, expert_ids, layer,
                     act, interpret)
    return y, (xe, w_gate, w_up, w_down, counts, expert_ids, layer)


def _kernel_call_bwd(act, interpret, res, g):
    # recompute through the differentiable oracle (the kernel and the
    # oracle agree on every kept row; dropped/tail rows carry no
    # gradient either way because their forward value is masked to zero).
    # With ``layer`` the oracle slices the stacks, so their cotangents
    # are zero everywhere but at that layer
    xe, w_gate, w_up, w_down, counts, expert_ids, layer = res
    _, vjp = jax.vjp(
        lambda x, wg, wu, wd: _oracle(x, wg, wu, wd, counts, expert_ids,
                                      act, layer),
        xe, w_gate, w_up, w_down)
    dxe, dwg, dwu, dwd = vjp(g)
    # int operands take float0 cotangents
    zero = lambda a: (None if a is None
                      else np.zeros(np.shape(a), jax.dtypes.float0))
    return (dxe, dwg, dwu, dwd, zero(counts), zero(expert_ids),
            zero(layer))


_kernel_call.defvjp(_kernel_call_fwd, _kernel_call_bwd)


def expert_ffn_op(xe, w_gate, w_up, w_down, act: str = "silu",
                  counts=None, expert_ids=None, layer=None,
                  force_kernel: bool = False,
                  interpret: bool | None = None):
    on_tpu = jax.default_backend() == "tpu"
    if on_tpu or force_kernel:
        if layer is not None and w_gate.shape[-1] % _sublane(xe.dtype):
            # the kernel pads an f off the sublane tile, which it may not
            # do to a whole stack: it gets the layer's slice instead
            w_gate, w_up, w_down = _layer_slice(layer, w_gate, w_up, w_down)
            layer = None
        return _kernel_call(xe, w_gate, w_up, w_down, counts, expert_ids,
                            layer, act,
                            (not on_tpu) if interpret is None
                            else interpret)
    return _oracle(xe, w_gate, w_up, w_down, counts, expert_ids, act, layer)
