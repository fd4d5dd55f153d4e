"""Seeded random weights, made by the benchmark and not by the program.

Every value is exact before its one rounding: 16 random bits read as a
signed integer, times a power of two, rounded once to bfloat16.  So the
program's copy (made in one jitted call for the whole model) and the
reference's copy (made leaf by leaf, layer by layer) are bit-identical
whatever XLA fuses.  Values are uniform on [-a, a) with a = 2**a_exp
chosen from the fan-in (std a/sqrt(3) ~ 1/sqrt(fan_in)); embeddings have
std ~1; norm scales are 1 + delta with |delta| < 1/16.

Keys: leaf key = fold_in(fold_in(fold_in(base, layer + 1), leaf), expert)
with layer -1 for the leaves outside the layers, base from both 32-bit
halves of the seed.  Which leaves a model has, their ids and shapes, is
its family's (``bench/families/``).
"""
from __future__ import annotations

import math

import jax
import jax.numpy as jnp

NORM_EXP = -4          # norm deltas uniform on [-1/16, 1/16)
EMBED_EXP = 1          # embedding rows uniform on [-2, 2): std 1.15


def seed_halves(seed: int):
    """(lo, hi) uint32 halves of a non-negative seed of up to 64 bits."""
    seed = int(seed)
    if seed < 0:
        raise ValueError(f"seed must be >= 0, got {seed}")
    return (jnp.uint32(seed & 0xFFFFFFFF), jnp.uint32((seed >> 32)
                                                      & 0xFFFFFFFF))


def base_key(lo, hi):
    return jax.random.fold_in(jax.random.PRNGKey(lo), hi)


def fan_in_exp(fan_in: int) -> int:
    """a_exp with 2**a_exp nearest sqrt(3 / fan_in) in log2."""
    return round(math.log2(math.sqrt(3.0 / fan_in)))


def leaf(base, layer, leaf_id: int, expert, shape, a_exp: int,
         dtype=jnp.bfloat16):
    """One leaf: uniform 16-bit integers times 2**(a_exp - 15), rounded
    once to ``dtype``.  ``layer`` and ``expert`` may be traced."""
    k = jax.random.fold_in(jax.random.fold_in(
        jax.random.fold_in(base, layer + 1), leaf_id), expert)
    bits = jax.random.bits(k, shape, jnp.uint16)
    ints = jax.lax.bitcast_convert_type(bits, jnp.int16)
    return (ints.astype(jnp.float32)
            * jnp.float32(2.0 ** (a_exp - 15))).astype(dtype)


def norm_scale(delta):
    """The RMSNorm scale a norm leaf stands for: 1 + delta (in f32)."""
    return 1.0 + delta.astype(jnp.float32)


def make_leaf(base, m: dict, name: str, layer=-1, expert=0):
    """Leaf ``name`` of configuration ``m``, as its family lays it out."""
    from bench.families import family
    fam = family(m)
    shape, a = fam.shapes(m)[name]
    return leaf(base, layer, fam.LEAVES[name], expert, shape, a)
