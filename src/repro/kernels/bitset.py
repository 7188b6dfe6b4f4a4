"""Pallas TPU kernels for the bit-packed Visited structures (DESIGN.md §2).

gIM keeps one byte-per-node ``Visited`` array per block in GPU global memory
(§3.5 shows this dominating memory: 465 GB if naively replicated).  The TPU
adaptation packs visited sets as (B, W=ceil(n/32)) uint32 — 32× smaller — and
these kernels provide the hot bit-level ops:

* :func:`pack_bits`       — (B, n) bool  -> (B, W) uint32
* :func:`bitset_or`       — visited |= new       (elementwise tiles)
* :func:`bitset_andnot`   — frontier = new & ~visited
* :func:`popcount_words`  — per-word popcount (SWAR)
* :func:`occur_from_bitset` — Occur[n] = Σ_lanes bit_v  (the paper's
  atomicAdd(Occur) recast as a cross-lane bit-column reduction: 32
  bit-plane MXU products per tile, accumulated over row blocks into a
  VMEM-resident (32, TW) histogram tile per node-word tile)
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu


def mxu_dtype(interpret: bool):
    """Operand dtype of the Occur kernel's 0/1 MXU products.

    Every operand value is exact in bf16 and every sum exact in the f32
    accumulator, so both choices give identical integers.  Compiled
    kernels feed the MXU bf16; interpret mode uses f32 because XLA:CPU
    has no bf16 x bf16 -> f32 dot."""
    return jnp.float32 if interpret else jnp.bfloat16


# ------------------------------------------------------------------ pack

def _pack_kernel(bits_ref, words_ref):
    bits = bits_ref[...]                       # (BB, n) bool
    bb, n = bits.shape
    w = n // 32
    b3 = bits.reshape(bb, w, 32).astype(jnp.uint32)
    shift = jax.lax.broadcasted_iota(jnp.uint32, (bb, w, 32), 2)
    words_ref[...] = (b3 << shift).sum(axis=2).astype(jnp.uint32)


@functools.partial(jax.jit, static_argnames=("block_b", "interpret"))
def pack_bits(bits: jnp.ndarray, *, block_b: int = 8, interpret: bool = True):
    b, n = bits.shape
    if n % 32:
        raise ValueError("n must be a multiple of 32 (pad first)")
    bb = min(block_b, b)
    return pl.pallas_call(
        _pack_kernel,
        grid=(pl.cdiv(b, bb),),
        in_specs=[pl.BlockSpec((bb, n), lambda i: (i, 0))],
        out_specs=pl.BlockSpec((bb, n // 32), lambda i: (i, 0)),
        out_shape=jax.ShapeDtypeStruct((b, n // 32), jnp.uint32),
        interpret=interpret,
    )(bits)


# ------------------------------------------------------- elementwise pair

def _or_kernel(a_ref, b_ref, o_ref):
    o_ref[...] = a_ref[...] | b_ref[...]


def _andnot_kernel(a_ref, b_ref, o_ref):
    o_ref[...] = a_ref[...] & ~b_ref[...]


def _binary_op(kernel, a, b, block_b, interpret):
    bsz, w = a.shape
    bb = min(block_b, bsz)
    return pl.pallas_call(
        kernel,
        grid=(pl.cdiv(bsz, bb),),
        in_specs=[pl.BlockSpec((bb, w), lambda i: (i, 0)),
                  pl.BlockSpec((bb, w), lambda i: (i, 0))],
        out_specs=pl.BlockSpec((bb, w), lambda i: (i, 0)),
        out_shape=jax.ShapeDtypeStruct((bsz, w), jnp.uint32),
        interpret=interpret,
    )(a, b)


@functools.partial(jax.jit, static_argnames=("block_b", "interpret"))
def bitset_or(a, b, *, block_b: int = 64, interpret: bool = True):
    return _binary_op(_or_kernel, a, b, block_b, interpret)


@functools.partial(jax.jit, static_argnames=("block_b", "interpret"))
def bitset_andnot(a, b, *, block_b: int = 64, interpret: bool = True):
    """a & ~b."""
    return _binary_op(_andnot_kernel, a, b, block_b, interpret)


# -------------------------------------------------------------- popcount

def _popcount(v: jnp.ndarray) -> jnp.ndarray:
    """SWAR popcount on uint32."""
    v = v - ((v >> 1) & jnp.uint32(0x55555555))
    v = (v & jnp.uint32(0x33333333)) + ((v >> 2) & jnp.uint32(0x33333333))
    v = (v + (v >> 4)) & jnp.uint32(0x0F0F0F0F)
    return (v * jnp.uint32(0x01010101)) >> 24


def _popcount_kernel(w_ref, o_ref):
    o_ref[...] = _popcount(w_ref[...]).astype(jnp.int32)


@functools.partial(jax.jit, static_argnames=("block_b", "interpret"))
def popcount_words(words, *, block_b: int = 64, interpret: bool = True):
    """Per-word popcount (e.g. RR-set sizes from packed membership)."""
    b, w = words.shape
    bb = min(block_b, b)
    return pl.pallas_call(
        _popcount_kernel,
        grid=(pl.cdiv(b, bb),),
        in_specs=[pl.BlockSpec((bb, w), lambda i: (i, 0))],
        out_specs=pl.BlockSpec((bb, w), lambda i: (i, 0)),
        out_shape=jax.ShapeDtypeStruct((b, w), jnp.int32),
        interpret=interpret,
    )(words)


# ------------------------------------------------------ occur histogram

def _occur_kernel(words_ref, keep_ref, out_ref, *, n_rows: int, dt):
    """One (row block, word tile) step of the bit-plane histogram.

    ``out[b, w] += Σ_r keep[r] · bit_b(words[r, w])``: each of the 32 bit
    planes is one (1, BB) x (BB, TW) MXU product against the row mask, so
    the lane mask needs no relayout and the counts (≤ BB per step) are
    exact in f32.  Rows past ``n_rows`` (a partial last block) are masked.
    """
    i = pl.program_id(1)

    @pl.when(i == 0)
    def _init():
        out_ref[...] = jnp.zeros_like(out_ref)

    words = words_ref[...].astype(jnp.int32)                 # (BB, TW)
    bb = words.shape[0]
    row = jax.lax.broadcasted_iota(jnp.int32, (1, bb), 1) + i * bb
    keep = jnp.where(row < n_rows, keep_ref[...], 0)
    keep = jnp.broadcast_to(keep.astype(dt), (8, bb))
    for b in range(32):
        plane = (jax.lax.shift_right_logical(words, b) & 1).astype(dt)
        cnt = jnp.dot(keep, plane, preferred_element_type=jnp.float32)
        out_ref[b:b + 1, :] += cnt[0:1].astype(jnp.int32)


@functools.partial(jax.jit,
                   static_argnames=("block_b", "block_w", "interpret"))
def occur_from_bitset_masked(words, rowmask, *, block_b: int = 256,
                             block_w: int = 1024, interpret: bool = True):
    """Occur[v] = number of *selected* lanes with bit v set.

    ``rowmask`` (B,) bool/int32 selects the lanes that contribute — this is
    the popcount-arithmetic Occur *decrement* of the fused greedy selection
    (dec over newly covered RR rows), replacing the per-seed flat scatter.

    The grid tiles node words (parallel) over row blocks (the innermost,
    accumulating axis), so VMEM holds one (BB, TW) word tile and one
    (32, TW) bit-plane accumulator at any node count.  The kernel writes
    the histogram bit-plane-major; one small XLA transpose restores node
    order.  Output length W*32.
    """
    b, w = words.shape
    bb, tw = min(block_b, b), min(block_w, w)
    planes = pl.pallas_call(
        functools.partial(_occur_kernel, n_rows=b, dt=mxu_dtype(interpret)),
        grid=(pl.cdiv(w, tw), pl.cdiv(b, bb)),
        in_specs=[pl.BlockSpec((bb, tw), lambda j, i: (i, j)),
                  pl.BlockSpec((1, bb), lambda j, i: (0, i))],
        out_specs=pl.BlockSpec((32, tw), lambda j, i: (0, j)),
        out_shape=jax.ShapeDtypeStruct((32, w), jnp.int32),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "arbitrary")),
        interpret=interpret,
    )(words, rowmask.astype(jnp.int32).reshape(1, b))
    return planes.T.reshape(w * 32)


def occur_from_bitset(words, *, interpret: bool = True):
    """Occur[v] = number of lanes with bit v set.  Output length W*32."""
    return occur_from_bitset_masked(
        words, jnp.ones(words.shape[0], jnp.int32), interpret=interpret)
