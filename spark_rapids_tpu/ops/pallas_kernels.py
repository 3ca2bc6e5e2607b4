"""Pallas TPU kernels — the hand-written tier below XLA.

SURVEY.md §7.1.3 left open whether hand-written Pallas kernels beat
XLA's fusion for this engine's hot loops (the reference's answer is
libcudf CUDA kernels for everything; the TPU bet was that XLA fusion
covers most of it). Two kernels are kept, each A/B-benchmarked against
the identical jnp/XLA formulation in bench.py, and each compiled for the
v5e by tests/test_chip_compile.py: `masked_product_sum` (the q6 inner
loop — filter conjuncts + product + reduction in ONE pass over VMEM
tiles; `pallas_ab`) and `fused_filter_agg` (the whole-stage-fusion
shape, a grouped partial reduction; `pallas_fused_agg_ab`). Neither has
a caller on the query path (ROADMAP D5).

Two more were deleted in PR 21 because the chip's compiler (Mosaic,
jax 0.9 / libtpu 0.0.34) refuses them and nothing called them: a 1-D
dynamic gather (`NotImplementedError: Only 2D gather is supported`) and
a fully unrolled bitonic sort network over sub-lane reshapes
(`RecursionError: maximum recursion depth exceeded` while lowering its
136 stages). Both had only ever run with `interpret=True`.

Each kernel is ONE grid-free `pallas_call` per VMEM-sized chunk, with the
chunks composed at the XLA level under one jit. That shape was chosen
when a compiler that is gone rejected every gridded kernel; on the v5e's
own compiler a GRIDDED edition of `masked_product_sum` compiles in 0.6 s
once its index maps return int32 (with x64 on, a bare `0` is an int64
and Mosaic fails to legalize the `func.return`) — PR 21 compiled both
forms and rewrote neither (ROADMAP D11). `interpret=True` keeps the
kernels runnable on the CPU test mesh.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

__all__ = ["masked_product_sum_pallas", "masked_product_sum_xla",
           "fused_filter_agg_pallas", "fused_filter_agg_xla",
           "FUSED_AGG_GROUPS"]

_TILE_ROWS = 2048
_LANES = 128


def masked_product_sum_xla(quantity, price, discount, shipdate):
    """The q6 inner loop as XLA sees it (what the engine's fused
    filter->project->agg pipeline lowers to)."""
    mask = ((shipdate >= 8766) & (shipdate < 9131)
            & (discount >= 0.05) & (discount <= 0.07)
            & (quantity < 24.0))
    return jnp.sum(jnp.where(mask, price * discount, 0.0),
                   dtype=jnp.float32)


def _kernel(q_ref, p_ref, d_ref, s_ref, out_ref):
    q = q_ref[...]
    p = p_ref[...]
    d = d_ref[...]
    s = s_ref[...]
    mask = ((s >= 8766) & (s < 9131) & (d >= 0.05) & (d <= 0.07)
            & (q < 24.0))
    vals = jnp.where(mask, p * d, 0.0)
    # reduce the (TILE_ROWS, 128) tile to a min-tile (8, 128) partial —
    # a (1, 1) accumulator is below the f32 tile floor and fails Mosaic
    out_ref[...] = jnp.sum(vals.reshape(-1, 8, _LANES), axis=0,
                           dtype=jnp.float32)


@functools.partial(jax.jit, static_argnums=(4,))
def masked_product_sum_pallas(quantity, price, discount, shipdate,
                              interpret: bool = False):
    """Pallas edition: one grid-free kernel invocation per VMEM-sized
    chunk, the chunking done at the XLA level (several pallas_call ops
    composed under one jit, partial (8, 128) tiles summed outside; see
    the module docstring for why, and what the v5e's compiler says of a
    gridded edition). Row count must be a multiple of _TILE_ROWS*_LANES
    (the bench pads; engine batches are power-of-two capacities
    anyway)."""
    from jax.experimental import pallas as pl
    n = quantity.shape[0]
    rows = n // _LANES
    chunks = rows // _TILE_ROWS
    call = pl.pallas_call(
        _kernel,
        out_shape=jax.ShapeDtypeStruct((8, _LANES), jnp.float32),
        interpret=interpret)
    parts = []
    for c in range(chunks):
        lo = c * _TILE_ROWS * _LANES
        hi = lo + _TILE_ROWS * _LANES
        shape2d = (_TILE_ROWS, _LANES)
        parts.append(call(quantity[lo:hi].reshape(shape2d),
                          price[lo:hi].reshape(shape2d),
                          discount[lo:hi].reshape(shape2d),
                          shipdate[lo:hi].reshape(shape2d)))
    return jnp.sum(jnp.stack(parts), dtype=jnp.float32)


# --- fused filter+partial-agg A/B: the whole-stage-fusion shape -------------
# PR "scan-rooted whole-stage fusion" moved the from-files hot loop to ONE
# XLA program per batch doing decode -> filter -> project -> partial-agg.
# The open Pallas question AT THE FUSED LEVEL (ISSUE 15c): does a hand
# kernel beat the fused XLA chain on the chain's own shape — filter
# conjuncts + product + GROUPED partial reduction in one VMEM pass —
# rather than the global reduction masked_product_sum already measured?
# bench.py A/Bs it as `pallas_fused_agg_ab`; a kernel that does not
# compile on the chip, or disagrees with the XLA chain, fails the run.

FUSED_AGG_GROUPS = 8  # static group count: a partial-agg keyspace slice


def fused_filter_agg_xla(key, quantity, price, discount, shipdate):
    """The fused chain as the engine's XLA path sees it: q6's filter
    conjuncts, the price*discount projection, and a grouped partial sum
    over a small static keyspace (the segment-reduce shape the
    partial-agg tail lowers to; static one-hot per group — no scatter,
    matching the engine's gather/sort-only idiom). Returns float32
    per-group sums of shape (FUSED_AGG_GROUPS,)."""
    mask = ((shipdate >= 8766) & (shipdate < 9131)
            & (discount >= 0.05) & (discount <= 0.07)
            & (quantity < 24.0))
    vals = jnp.where(mask, price * discount, 0.0)
    return jnp.stack([
        jnp.sum(jnp.where(key == g, vals, 0.0), dtype=jnp.float32)
        for g in range(FUSED_AGG_GROUPS)])


def _fused_agg_kernel(k_ref, q_ref, p_ref, d_ref, s_ref, o_ref):
    k = k_ref[...]
    q = q_ref[...]
    p = p_ref[...]
    d = d_ref[...]
    s = s_ref[...]
    mask = ((s >= 8766) & (s < 9131) & (d >= 0.05) & (d <= 0.07)
            & (q < 24.0))
    vals = jnp.where(mask, p * d, 0.0)
    parts = []
    for g in range(FUSED_AGG_GROUPS):  # static keyspace: unrolled
        vg = jnp.where(k == g, vals, 0.0)
        # per-group (8, 128) min-tile partial — a (1, 1) accumulator is
        # below the f32 tile floor and fails Mosaic (see _kernel above)
        parts.append(jnp.sum(vg.reshape(-1, 8, _LANES), axis=0,
                             dtype=jnp.float32))
    o_ref[...] = jnp.concatenate(parts, axis=0)  # (GROUPS*8, 128)


@functools.partial(jax.jit, static_argnums=(5,))
def fused_filter_agg_pallas(key, quantity, price, discount, shipdate,
                            interpret: bool = False):
    """Pallas edition of the fused filter+partial-agg chain: grid-free
    chunked pallas_call like ``masked_product_sum_pallas``, each chunk
    emitting one (GROUPS*8, 128) partial block reduced outside. Row
    count must be a multiple of _TILE_ROWS*_LANES (the bench pads).
    Returns float32 per-group sums of shape (FUSED_AGG_GROUPS,)."""
    from jax.experimental import pallas as pl
    n = quantity.shape[0]
    rows = n // _LANES
    chunks = rows // _TILE_ROWS
    call = pl.pallas_call(
        _fused_agg_kernel,
        out_shape=jax.ShapeDtypeStruct((FUSED_AGG_GROUPS * 8, _LANES),
                                       jnp.float32),
        interpret=interpret)
    parts = []
    shape2d = (_TILE_ROWS, _LANES)
    for c in range(chunks):
        lo = c * _TILE_ROWS * _LANES
        hi = lo + _TILE_ROWS * _LANES
        parts.append(call(key[lo:hi].reshape(shape2d),
                          quantity[lo:hi].reshape(shape2d),
                          price[lo:hi].reshape(shape2d),
                          discount[lo:hi].reshape(shape2d),
                          shipdate[lo:hi].reshape(shape2d)))
    stacked = jnp.stack(parts)  # (chunks, GROUPS*8, 128)
    return jnp.sum(
        stacked.reshape(len(parts), FUSED_AGG_GROUPS, 8, _LANES),
        axis=(0, 2, 3), dtype=jnp.float32)
