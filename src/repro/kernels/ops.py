"""Jit'd public wrappers for the Pallas kernels.

``interpret`` defaults to backend-aware dispatch: interpret mode on CPU
(this container), compiled Mosaic on an accelerator backend.  The backend
is consulted lazily at call time — resolving it at import would initialize
JAX's platform as a side effect and freeze a stale choice.  The module
level ``INTERPRET`` override is kept for tests and debugging — set
``repro.kernels.ops.INTERPRET = True/False`` to force either mode for every
kernel at once (per-call ``interpret=`` still wins); ``None`` means auto.
"""
from __future__ import annotations

import os

import jax
import jax.numpy as jnp

from repro.kernels import membership as _membership
from repro.kernels import bernoulli as _bernoulli
from repro.kernels import bitset as _bitset

INTERPRET: bool | None = None    # None = auto: cpu -> interpret

_ENV_FLAG = "REPRO_KERNELS_INTERPRET"   # CI interpret-mode job sets this


def _env_interpret() -> bool | None:
    v = os.environ.get(_ENV_FLAG, "").strip().lower()
    if v in ("1", "true", "yes", "on"):
        return True
    if v in ("0", "false", "no", "off"):
        return False
    return None


def resolve_interpret(interpret: bool | None = None) -> bool:
    """Per-call flag > module override > env override > backend default."""
    if interpret is not None:
        return interpret
    if INTERPRET is not None:
        return INTERPRET
    env = _env_interpret()
    if env is not None:
        return env
    return jax.default_backend() == "cpu"


def membership_rows(rows, lengths, u, *, block_rows: int = 256,
                    interpret: bool | None = None):
    return _membership.membership_rows(
        rows, lengths, u, block_rows=block_rows,
        interpret=resolve_interpret(interpret))


def bernoulli_edges(weights, seed, *, block: int = 1024,
                    interpret: bool | None = None):
    return _bernoulli.bernoulli_edges(
        weights, seed, block=block,
        interpret=resolve_interpret(interpret))


def pack_bits(bits, *, interpret: bool | None = None):
    return _bitset.pack_bits(
        bits, interpret=resolve_interpret(interpret))


def bitset_or(a, b, *, interpret: bool | None = None):
    return _bitset.bitset_or(
        a, b, interpret=resolve_interpret(interpret))


def bitset_andnot(a, b, *, interpret: bool | None = None):
    return _bitset.bitset_andnot(
        a, b, interpret=resolve_interpret(interpret))


def popcount_words(words, *, interpret: bool | None = None):
    return _bitset.popcount_words(
        words, interpret=resolve_interpret(interpret))


def occur_from_bitset(words, *, interpret: bool | None = None):
    return _bitset.occur_from_bitset(
        words, interpret=resolve_interpret(interpret))


def occur_from_bitset_masked(words, rowmask, *, interpret: bool | None = None):
    return _bitset.occur_from_bitset_masked(
        words, rowmask, interpret=resolve_interpret(interpret))


def sketch_union_popcount(words, cov, *, interpret: bool | None = None):
    from repro.kernels import sketch as _sketch
    return _sketch.sketch_union_popcount(
        words, cov, interpret=resolve_interpret(interpret))


def flash_attention(q, k, v, *, causal=True, bq=128, bk=128,
                    interpret: bool | None = None):
    from repro.kernels import flashattn as _fa
    return _fa.flash_attention(
        q, k, v, causal=causal, bq=bq, bk=bk,
        interpret=resolve_interpret(interpret))
