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
work exactly like local empty buckets."""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

_ACTS = {"silu": jax.nn.silu, "gelu": jax.nn.gelu, "relu": jax.nn.relu}


def _kernel(x_ref, wg_ref, wu_ref, wd_ref, o_ref, *, act, n_fi):
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


def _kernel_grouped(counts_ref, eids_ref, x_ref, wg_ref, wu_ref, wd_ref,
                    o_ref, *, act, bc):
    # identical compute to _kernel_ragged; eids_ref is consumed by the
    # weight BlockSpec index maps, not the body
    del eids_ref
    _kernel_ragged(counts_ref, x_ref, wg_ref, wu_ref, wd_ref, o_ref,
                   act=act, bc=bc)


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
               interpret: bool = False, expert_ids=None):
    """xe (E, C, d); w_gate/w_up (E, d, f); w_down (E, f, d) -> (E, C, d).

    With ``counts`` (E,) int32 — tokens actually packed per expert — the
    ragged skip-empty kernel runs; blocks entirely above counts[e] produce
    zeros without touching the MXU.

    With ``expert_ids`` (G,) int32 as well, xe is (G, C, d) row groups and
    group g computes against weight set expert_ids[g] (expert-parallel
    receive buckets: one group per (source device, local expert))."""
    if expert_ids is not None and counts is None:
        raise ValueError("expert_ids requires counts (grouped ragged)")
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
    grid = (E, C // bc, f // bf)
    out_shape = jax.ShapeDtypeStruct((E, C, d), jnp.float32)
    params = pltpu.CompilerParams(vmem_limit_bytes=_vmem_limit_bytes(
        bc, bf, d, jnp.dtype(xe.dtype).itemsize))

    if counts is None:
        y = pl.pallas_call(
            functools.partial(_kernel, act=act, n_fi=f // bf),
            grid=grid,
            in_specs=[
                pl.BlockSpec((1, bc, d), lambda e, ci, fi: (e, ci, 0)),
                pl.BlockSpec((1, d, bf), lambda e, ci, fi: (e, 0, fi)),
                pl.BlockSpec((1, d, bf), lambda e, ci, fi: (e, 0, fi)),
                pl.BlockSpec((1, bf, d), lambda e, ci, fi: (e, fi, 0)),
            ],
            out_specs=pl.BlockSpec((1, bc, d), lambda e, ci, fi: (e, ci, 0)),
            out_shape=out_shape,
            compiler_params=params,
            interpret=interpret,
        )(xe, w_gate, w_up, w_down)
        return y[:, :C_in].astype(xe.dtype)

    if expert_ids is not None:
        # grouped ragged: counts AND the group→expert map ride ahead of
        # the grid as scalar-prefetch operands (SMEM); the map drives the
        # weight index maps so no gathered weight copies materialise
        grid_spec = pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=2,
            grid=grid,
            in_specs=[
                pl.BlockSpec((1, bc, d),
                             lambda g, ci, fi, c, eid: (g, ci, 0)),
                pl.BlockSpec((1, d, bf),
                             lambda g, ci, fi, c, eid: (eid[g], 0, fi)),
                pl.BlockSpec((1, d, bf),
                             lambda g, ci, fi, c, eid: (eid[g], 0, fi)),
                pl.BlockSpec((1, bf, d),
                             lambda g, ci, fi, c, eid: (eid[g], fi, 0)),
            ],
            out_specs=pl.BlockSpec((1, bc, d),
                                   lambda g, ci, fi, c, eid: (g, ci, 0)),
        )
        y = pl.pallas_call(
            functools.partial(_kernel_grouped, act=act, bc=bc),
            grid_spec=grid_spec,
            out_shape=out_shape,
            compiler_params=params,
            interpret=interpret,
        )(counts.astype(jnp.int32), expert_ids.astype(jnp.int32),
          xe, w_gate, w_up, w_down)
        return y[:, :C_in].astype(xe.dtype)

    # ragged: counts ride ahead of the grid as a scalar-prefetch operand
    # (SMEM), so the pl.when guard reads them before any block compute
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=1,
        grid=grid,
        in_specs=[
            pl.BlockSpec((1, bc, d), lambda e, ci, fi, c: (e, ci, 0)),
            pl.BlockSpec((1, d, bf), lambda e, ci, fi, c: (e, 0, fi)),
            pl.BlockSpec((1, d, bf), lambda e, ci, fi, c: (e, 0, fi)),
            pl.BlockSpec((1, bf, d), lambda e, ci, fi, c: (e, fi, 0)),
        ],
        out_specs=pl.BlockSpec((1, bc, d), lambda e, ci, fi, c: (e, ci, 0)),
    )
    y = pl.pallas_call(
        functools.partial(_kernel_ragged, act=act, bc=bc),
        grid_spec=grid_spec,
        out_shape=out_shape,
        compiler_params=params,
        interpret=interpret,
    )(counts.astype(jnp.int32), xe, w_gate, w_up, w_down)
    return y[:, :C_in].astype(xe.dtype)
