"""Pallas TPU kernel: grouped (batched-per-expert) gated FFN.

Computes, for every expert e:  y_e = act(x_e @ Wg_e) * (x_e @ Wu_e) @ Wd_e
over capacity-bucketed token blocks x (E, C, d) — the compute hot spot of
MoE offloading inference (paper §2.1/Fig. 2: the expert FFN is what gets
scheduled between devices; on TPU it is the MXU-bound inner loop).

Tiling: grid (E, C/bc, f/bf), f innermost so the (bc, d) f32 output block
accumulates partial down-projections in VMEM across the f sweep:

  x block     (bc, d)   — revisited across fi           ~ bc*d*2   bytes
  Wg/Wu block (d, bf)   — streamed per (e, fi)          ~ d*bf*2*2
  Wd block    (bf, d)   — streamed per (e, fi)          ~ bf*d*2
  out block   (bc, d)   — f32 accumulator, revisited    ~ bc*d*4
  counts      (E,)      — scalar-prefetched to SMEM (ragged variant)
  layer       (1,)      — scalar-prefetched to SMEM (stacked variant)

Block sizes default to MXU-friendly multiples of 128 and are clamped to
the problem size.  All matmuls accumulate in f32
(preferred_element_type), output cast to the input dtype.

Scoped VMEM: every block above is double-buffered, so at Mixtral widths
(d 4096, bf 512, bf16) the kernel needs ~33 MiB, over the compiler's
16 MiB default scoped limit.  The limit is raised to the blocks' own
footprint (``_vmem_limit_bytes``) rather than shrinking bf: the weights
stream from HBM once per (e, ci) whatever bf is, so smaller blocks only
add grid steps, and the f-block accumulation order (hence the numerics)
stays what the interpret-mode tests pin.  A TPU v5e core has 128 MiB.

The ragged variant takes per-expert token ``counts`` (the MoE workload
vector) via scalar prefetch and guards each (e, ci) block with ``pl.when``
so capacity blocks holding no real tokens skip their MXU work entirely
(MegaBlocks-style skip-empty; block DMAs still stream — the index maps are
unconditional).  Rows at/beyond counts[e] inside a partial block are
zeroed before the matmuls, so garbage in a bucket tail can never leak
into the output.

The grouped variant (``expert_ids``) generalises ragged to G row groups
sharing E weight sets: xe (G, C, d) with counts (G,) and a scalar-
prefetched group→expert map, whose ids drive the WEIGHT block index maps
(no gathered/replicated weight copies).  This is the expert-parallel
entry: each received bucket (source device, local expert) is one group
(models/moe_ep.py), so blocks a remote device sent empty skip their MXU
work exactly like local empty buckets.

The stacked variant (``layer``) reads one layer's weights in place from
the model's (L, E, d, f) / (L, E, f, d) scan stacks: a scalar-prefetched
layer index leads the weight index maps, so the layer scan hands the
kernel the whole stacks instead of writing a (E, d, f) slice of each per
call (models/model.py)."""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

_ACTS = {"silu": jax.nn.silu, "gelu": jax.nn.gelu, "relu": jax.nn.relu}


def _kernel(x_ref, wg_ref, wu_ref, wd_ref, o_ref, *, act):
    fi = pl.program_id(2)

    @pl.when(fi == 0)
    def _init():
        o_ref[...] = jnp.zeros_like(o_ref)

    x = x_ref[0]                                   # (bc, d)
    wg = wg_ref[0]                                 # (d, bf)
    wu = wu_ref[0]
    wd = wd_ref[0]                                 # (bf, d)
    h = _ACTS[act](jnp.dot(x, wg, preferred_element_type=jnp.float32))
    h = h * jnp.dot(x, wu, preferred_element_type=jnp.float32)
    o_ref[0] += jnp.dot(h.astype(wd.dtype), wd,
                        preferred_element_type=jnp.float32)


def _kernel_ragged(counts_ref, x_ref, wg_ref, wu_ref, wd_ref, o_ref, *,
                   act, bc):
    e, ci, fi = pl.program_id(0), pl.program_id(1), pl.program_id(2)
    n_tok = counts_ref[e]                          # this expert's workload

    @pl.when(fi == 0)
    def _init():
        o_ref[...] = jnp.zeros_like(o_ref)

    @pl.when(ci * bc < n_tok)                      # skip-empty: no MXU work
    def _compute():                                # for workload-free blocks
        x = x_ref[0]                               # (bc, d)
        row = ci * bc + jax.lax.broadcasted_iota(jnp.int32, x.shape, 0)
        x = jnp.where(row < n_tok, x, 0)           # mask partial-block tail
        wg = wg_ref[0]                             # (d, bf)
        wu = wu_ref[0]
        wd = wd_ref[0]                             # (bf, d)
        h = _ACTS[act](jnp.dot(x, wg, preferred_element_type=jnp.float32))
        h = h * jnp.dot(x, wu, preferred_element_type=jnp.float32)
        o_ref[0] += jnp.dot(h.astype(wd.dtype), wd,
                            preferred_element_type=jnp.float32)


def _sublane(dtype) -> int:
    """Minimum second-minor tile dim per dtype (TPU layout constraint)."""
    return {jnp.dtype(jnp.bfloat16): 16, jnp.dtype(jnp.int8): 32}.get(
        jnp.dtype(dtype), 8)


_MIB = 1 << 20


def _vmem_limit_bytes(bc: int, bf: int, d: int, itemsize: int) -> int:
    """Scoped-VMEM limit for one grid step: the double-buffered x, Wg,
    Wu, Wd and f32 output blocks plus the step's f32 intermediates (the
    two (bc, bf) projections and the (bc, d) down-projection partial),
    with 25% headroom, never below the compiler's 16 MiB default."""
    blocks = 2 * (bc * d * itemsize + 3 * d * bf * itemsize + bc * d * 4)
    scratch = 3 * bc * bf * 4 + bc * d * 4
    need = -(-(blocks + scratch) * 5 // 4 // _MIB) * _MIB
    return max(16 * _MIB, need)


def _block_size(n: int, target: int, unit: int = 1) -> int:
    """Largest divisor of n that is <= target and a multiple of ``unit``
    (the sublane tile), so arbitrary problem shapes — capacities pad to
    multiples of 4, d_expert need not divide block_f — tile without
    remainder blocks or sub-tile sublane dims.  Requires unit | n (the
    caller pads n first)."""
    for b in range(min(target, n), 0, -1):
        if n % b == 0 and b % unit == 0:
            return b
    return n


@functools.partial(jax.jit, static_argnames=("act", "block_c", "block_f",
                                             "interpret"))
def expert_ffn(xe, w_gate, w_up, w_down, counts=None, act: str = "silu",
               block_c: int = 128, block_f: int = 512,
               interpret: bool = False, expert_ids=None, layer=None):
    """xe (E, C, d); w_gate/w_up (E, d, f); w_down (E, f, d) -> (E, C, d).

    With ``counts`` (E,) int32 — tokens actually packed per expert — the
    ragged skip-empty kernel runs; blocks entirely above counts[e] produce
    zeros without touching the MXU.

    With ``expert_ids`` (G,) int32 as well, xe is (G, C, d) row groups and
    group g computes against weight set expert_ids[g] (expert-parallel
    receive buckets: one group per (source device, local expert)).

    With ``layer`` (an int32 scalar or (1,)), the weights are whole
    per-layer stacks — w_gate/w_up (L, E, d, f), w_down (L, E, f, d) —
    and the kernel reads layer ``layer``'s blocks where they lie: the
    index rides as a scalar-prefetch operand into the weight index maps,
    so no (E, d, f) slice of the stack is ever materialised.  Each block
    computes exactly what the call on ``w[layer]`` computes.  The stacks
    are never copied, so f must already be a multiple of the sublane
    tile (no padding of the weights): otherwise this raises and the
    caller slices the layer itself."""
    if expert_ids is not None and counts is None:
        raise ValueError("expert_ids requires counts (grouped ragged)")
    if expert_ids is not None and layer is not None:
        raise ValueError("layer and expert_ids do not combine")
    E, C, d = xe.shape
    f = w_gate.shape[-1]
    # pad the sublane-facing dims (token rows; f as Wd's row dim) to the
    # dtype tile so Mosaic never sees a sub-tile block: zero rows/columns
    # contribute zero, and the output is sliced back below
    sub = _sublane(xe.dtype)
    C_in = C
    C_pad = -(-C // sub) * sub
    f_pad = -(-f // sub) * sub
    if C_pad != C:
        xe = jnp.pad(xe, ((0, 0), (0, C_pad - C), (0, 0)))
    if f_pad != f:
        if layer is not None:
            raise ValueError(f"stacked weights need f ({f}) to be a "
                             f"multiple of the sublane tile ({sub}); pass "
                             "the layer's slice without layer=")
        w_gate = jnp.pad(w_gate, ((0, 0), (0, 0), (0, f_pad - f)))
        w_up = jnp.pad(w_up, ((0, 0), (0, 0), (0, f_pad - f)))
        w_down = jnp.pad(w_down, ((0, 0), (0, f_pad - f), (0, 0)))
    C, f = C_pad, f_pad
    bc = _block_size(C, block_c, sub)
    bf = _block_size(f, block_f, sub)
    if bf % 128 and f % 128 == 0:
        # bf is the lane dim of the (d, bf) weight blocks: a multiple of
        # 128 on TPU (DeepSeek-V2-Lite's f = 1408 = 11 x 128 gives 128)
        bf = _block_size(f, block_f, 128)
    params = pltpu.CompilerParams(vmem_limit_bytes=_vmem_limit_bytes(
        bc, bf, d, jnp.dtype(xe.dtype).itemsize))

    # scalar-prefetch operands ride ahead of the grid into SMEM, in this
    # order: the layer index and the group->expert map drive the WEIGHT
    # index maps (no gathered or sliced weight copies materialise);
    # counts feed the ragged body's pl.when guard
    prefetch = []
    if layer is not None:
        prefetch.append(jnp.reshape(layer, (1,)).astype(jnp.int32))
    if counts is not None:
        prefetch.append(counts.astype(jnp.int32))
    if expert_ids is not None:
        prefetch.append(expert_ids.astype(jnp.int32))

    if layer is not None:                    # (L, E, ...) stacks: [layer, e]
        lead, wblock = (lambda e, pre: (pre[0][0], e)), (None, 1)
    elif expert_ids is not None:             # (E, ...) sets: [expert_ids[g]]
        lead, wblock = (lambda e, pre: (pre[-1][e],)), (1,)
    else:
        lead, wblock = (lambda e, pre: (e,)), (1,)
    rows = pl.BlockSpec((1, bc, d), lambda e, ci, fi, *pre: (e, ci, 0))
    w_in = pl.BlockSpec((*wblock, d, bf),
                        lambda e, ci, fi, *pre: (*lead(e, pre), 0, fi))
    w_out = pl.BlockSpec((*wblock, bf, d),
                         lambda e, ci, fi, *pre: (*lead(e, pre), fi, 0))

    def body(*refs):
        pre, blocks = refs[:len(prefetch)], refs[len(prefetch):]
        if counts is None:
            return _kernel(*blocks, act=act)
        return _kernel_ragged(pre[int(layer is not None)], *blocks,
                              act=act, bc=bc)

    y = pl.pallas_call(
        body,
        name="expert_ffn",
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=len(prefetch),
            grid=(E, C // bc, f // bf),
            in_specs=[rows, w_in, w_in, w_out],
            out_specs=rows,
        ),
        out_shape=jax.ShapeDtypeStruct((E, C, d), jnp.float32),
        compiler_params=params,
        interpret=interpret,
    )(*prefetch, xe, w_gate, w_up, w_down)
    return y[:, :C_in].astype(xe.dtype)
