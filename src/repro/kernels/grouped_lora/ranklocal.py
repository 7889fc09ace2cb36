"""Pallas TPU kernels: RANK-LOCAL grouped multi-adapter LoRA GEMMs.

The dense kernels (grouped_lora.py) implement rank heterogeneity purely by
zero-masking (paper §A.1): every slot is padded to ``r_max``, so a rank-4
adapter co-located with a rank-64 one pays 16x its true FLOPs and full
``r_max`` VMEM in every grouped GEMM. This module makes the true rank a
per-slot launch operand, carried by scalar prefetch beside the ragged
kernels' (ragged.py) token-row counts and composing with them:

  * two prefetched vectors ride every launch: ``rows: [Z] int32`` (valid
    token rows per slot — ragged widths) and ``ranks: [Z] int32`` (true
    rank per slot);
  * every block holds the WHOLE padded rank axis (Mosaic only accepts
    blocks whose last two dims are (8, 128)-aligned or span the array, and
    every configured ``r_max`` is at most 128 lanes, one MXU pass), so
    each kernel makes one rank-masked dot per tile; the skip is the
    whole-slot one (rank-0 / row-0 slots and row tiles past ``rows[z]``
    never touch the MXU under ``@pl.when``). With no rank tiles to skip
    these are the ragged kernels plus a rank mask;
  * the mask zero-masks A's columns / B's rows past ``ranks[z]`` on load, so
    correctness never depends on the padded rank region's contents — the
    post-step ``mask_lora_tree`` re-mask is provably redundant on this
    path (the padded region gets zero output and exactly zero gradient;
    tests/test_kernels_ranklocal.py asserts the train-step invariant);
  * all six kernels (fwd S=XA, Y=SB(+base); bwd dS, dX, dA, dB) carry
    both vectors, so batch raggedness and rank locality compose in one
    launch per kernel.

Accumulation note: a full-rank slot inside a MIXED-rank launch is
parity-level (not bitwise) vs the dense kernels. Bitwise equality at
``ranks == r_max`` is delivered one level up:
``ops.ranklocal_grouped_lora`` dispatches concrete full-rank calls to the
dense/ragged path, exactly as the executor's per-step dense-vs-ragged
dispatch already does for ``rows == T``.

interpret=True is the CPU CI harness, Mosaic is the TPU target.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.kernels.grouped_lora import grouped_lora as K

F32 = jnp.float32

_NT = (((1,), (1,)), ((), ()))     # a @ b.T
_TN = (((0,), (0,)), ((), ()))     # a.T @ b


def _row_mask(block: jnp.ndarray, valid: jnp.ndarray) -> jnp.ndarray:
    """Zero rows >= ``valid`` of a (rows, cols) tile."""
    idx = jax.lax.broadcasted_iota(jnp.int32, block.shape, 0)
    return jnp.where(idx < valid, block, jnp.zeros_like(block))


def _col_mask(block: jnp.ndarray, valid: jnp.ndarray) -> jnp.ndarray:
    """Zero columns >= ``valid`` of a (rows, cols) tile."""
    idx = jax.lax.broadcasted_iota(jnp.int32, block.shape, 1)
    return jnp.where(idx < valid, block, jnp.zeros_like(block))


# ---------------------------------------------------------------------------
# forward: S = X @ A          (grid: Z x token-tiles x K)
# ---------------------------------------------------------------------------

def _xa_kernel(rows_ref, ranks_ref, x_ref, a_ref, s_ref, acc_ref):
    z, m, k = pl.program_id(0), pl.program_id(1), pl.program_id(2)
    vrow = rows_ref[z] - m * x_ref.shape[1]
    rank = ranks_ref[z]

    @pl.when(k == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)

    @pl.when((vrow > 0) & (rank > 0))      # dead slots/rows skip the MXU
    def _acc():
        xm = _row_mask(x_ref[0], vrow)
        am = _col_mask(a_ref[0], rank)
        acc_ref[...] += jnp.dot(xm, am, preferred_element_type=F32)

    @pl.when(k == pl.num_programs(2) - 1)
    def _done():
        s_ref[0] = acc_ref[...].astype(s_ref.dtype)


def xa(x: jnp.ndarray, A: jnp.ndarray, rows: jnp.ndarray,
       ranks: jnp.ndarray, *, bm: int = K.BM, bk: int = K.BK,
       interpret: bool = False) -> jnp.ndarray:
    """x: [Z,T,din], A: [Z,din,r] -> S [Z,T,r]; rank columns past
    ranks[z] (and token rows past rows[z]) are zero."""
    Z, T, din = x.shape
    r = A.shape[2]
    bm, bk = min(bm, T), min(bk, din)
    return pl.pallas_call(
        _xa_kernel,
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=2,
            grid=(Z, T // bm, din // bk),
            in_specs=[
                pl.BlockSpec((1, bm, bk), lambda z, m, k, rr, rk: (z, m, k)),
                pl.BlockSpec((1, bk, r), lambda z, m, k, rr, rk: (z, k, 0)),
            ],
            out_specs=pl.BlockSpec((1, bm, r),
                                   lambda z, m, k, rr, rk: (z, m, 0)),
            scratch_shapes=[pltpu.VMEM((bm, r), F32)],
        ),
        out_shape=jax.ShapeDtypeStruct((Z, T, r), x.dtype),
        interpret=interpret,
    )(rows.astype(jnp.int32), ranks.astype(jnp.int32), x, A)


# ---------------------------------------------------------------------------
# forward: Y = S @ B * scale (+ Y_base) — rank is the CONTRACTION
# ---------------------------------------------------------------------------

def _sb_kernel(scale_ref, rows_ref, ranks_ref, s_ref, b_ref, *refs,
               has_base):
    ybase_ref = refs[0] if has_base else None
    y_ref, acc_ref = refs[-2:]
    z, m = pl.program_id(0), pl.program_id(1)
    vrow = rows_ref[z] - m * s_ref.shape[1]
    rank = ranks_ref[z]
    acc_ref[...] = jnp.zeros_like(acc_ref)

    @pl.when((vrow > 0) & (rank > 0))
    def _acc():
        sm = _row_mask(s_ref[0], vrow)
        bm_ = _row_mask(b_ref[0], rank)
        acc_ref[...] += jnp.dot(sm, bm_, preferred_element_type=F32)

    y = acc_ref[...] * scale_ref[z]
    if has_base:                               # dead slots: base passthrough
        y = y + ybase_ref[0].astype(F32)
    y_ref[0] = y.astype(y_ref.dtype)


def sb_add(s: jnp.ndarray, B: jnp.ndarray, scale: jnp.ndarray,
           rows: jnp.ndarray, ranks: jnp.ndarray, y_base=None, *,
           bm: int = K.BM, bn: int = K.BN,
           interpret: bool = False) -> jnp.ndarray:
    """s: [Z,T,r], B: [Z,r,dout] -> Y [Z,T,dout]; the r contraction only
    sees B rows below ranks[z]."""
    Z, T, r = s.shape
    dout = B.shape[2]
    bm, bn = min(bm, T), min(bn, dout)
    in_specs = [
        pl.BlockSpec((1, bm, r), lambda z, m, n, sc, rr, rk: (z, m, 0)),
        pl.BlockSpec((1, r, bn), lambda z, m, n, sc, rr, rk: (z, 0, n)),
    ]
    args = [s, B]
    if y_base is not None:
        in_specs.append(
            pl.BlockSpec((1, bm, bn), lambda z, m, n, sc, rr, rk: (z, m, n)))
        args.append(y_base)
    return pl.pallas_call(
        functools.partial(_sb_kernel, has_base=y_base is not None),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=3,
            grid=(Z, T // bm, dout // bn),
            in_specs=in_specs,
            out_specs=pl.BlockSpec((1, bm, bn),
                                   lambda z, m, n, sc, rr, rk: (z, m, n)),
            scratch_shapes=[pltpu.VMEM((bm, bn), F32)],
        ),
        out_shape=jax.ShapeDtypeStruct((Z, T, dout), s.dtype),
        interpret=interpret,
    )(scale.astype(F32), rows.astype(jnp.int32), ranks.astype(jnp.int32),
      *args)


# ---------------------------------------------------------------------------
# backward: dS = scale * dY @ B^T     (rank is the OUTPUT columns)
# ---------------------------------------------------------------------------

def _ds_kernel(scale_ref, rows_ref, ranks_ref, dy_ref, b_ref, ds_ref,
               acc_ref):
    z, m, k = pl.program_id(0), pl.program_id(1), pl.program_id(2)
    vrow = rows_ref[z] - m * dy_ref.shape[1]
    rank = ranks_ref[z]

    @pl.when(k == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)

    @pl.when((vrow > 0) & (rank > 0))
    def _acc():
        dym = _row_mask(dy_ref[0], vrow)
        bm_ = _row_mask(b_ref[0], rank)
        acc_ref[...] += jax.lax.dot_general(dym, bm_, _NT,
                                            preferred_element_type=F32)

    @pl.when(k == pl.num_programs(2) - 1)
    def _done():
        ds_ref[0] = (acc_ref[...] * scale_ref[z]).astype(ds_ref.dtype)


def ds(dy: jnp.ndarray, B: jnp.ndarray, scale: jnp.ndarray,
       rows: jnp.ndarray, ranks: jnp.ndarray, *, bm: int = K.BM,
       bk: int = K.BK, interpret: bool = False) -> jnp.ndarray:
    """dy: [Z,T,dout], B: [Z,r,dout] -> dS [Z,T,r]; columns past ranks[z]
    are exactly zero (B's rows past the rank are masked)."""
    Z, T, dout = dy.shape
    r = B.shape[1]
    bm, bk = min(bm, T), min(bk, dout)
    return pl.pallas_call(
        _ds_kernel,
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=3,
            grid=(Z, T // bm, dout // bk),
            in_specs=[
                pl.BlockSpec((1, bm, bk),
                             lambda z, m, k, sc, rr, rk: (z, m, k)),
                pl.BlockSpec((1, r, bk),
                             lambda z, m, k, sc, rr, rk: (z, 0, k)),
            ],
            out_specs=pl.BlockSpec((1, bm, r),
                                   lambda z, m, k, sc, rr, rk: (z, m, 0)),
            scratch_shapes=[pltpu.VMEM((bm, r), F32)],
        ),
        out_shape=jax.ShapeDtypeStruct((Z, T, r), dy.dtype),
        interpret=interpret,
    )(scale.astype(F32), rows.astype(jnp.int32), ranks.astype(jnp.int32),
      dy, B)


# ---------------------------------------------------------------------------
# backward: dX = dS @ A^T             (rank is the CONTRACTION)
# ---------------------------------------------------------------------------

def _dx_kernel(rows_ref, ranks_ref, ds_ref, a_ref, dx_ref, acc_ref):
    z, m = pl.program_id(0), pl.program_id(1)
    vrow = rows_ref[z] - m * ds_ref.shape[1]
    rank = ranks_ref[z]
    acc_ref[...] = jnp.zeros_like(acc_ref)

    @pl.when((vrow > 0) & (rank > 0))
    def _acc():
        dsm = _row_mask(ds_ref[0], vrow)
        am = _col_mask(a_ref[0], rank)
        acc_ref[...] += jax.lax.dot_general(dsm, am, _NT,
                                            preferred_element_type=F32)

    dx_ref[0] = acc_ref[...].astype(dx_ref.dtype)


def dx(ds_: jnp.ndarray, A: jnp.ndarray, rows: jnp.ndarray,
       ranks: jnp.ndarray, *, bm: int = K.BM, bn: int = K.BN,
       interpret: bool = False) -> jnp.ndarray:
    """ds: [Z,T,r], A: [Z,din,r] -> dX [Z,T,din]; only A's columns below
    ranks[z] enter the contraction."""
    Z, T, r = ds_.shape
    din = A.shape[1]
    bm, bn = min(bm, T), min(bn, din)
    return pl.pallas_call(
        _dx_kernel,
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=2,
            grid=(Z, T // bm, din // bn),
            in_specs=[
                pl.BlockSpec((1, bm, r), lambda z, m, n, rr, rk: (z, m, 0)),
                pl.BlockSpec((1, bn, r), lambda z, m, n, rr, rk: (z, n, 0)),
            ],
            out_specs=pl.BlockSpec((1, bm, bn),
                                   lambda z, m, n, rr, rk: (z, m, n)),
            scratch_shapes=[pltpu.VMEM((bm, bn), F32)],
        ),
        out_shape=jax.ShapeDtypeStruct((Z, T, din), ds_.dtype),
        interpret=interpret,
    )(rows.astype(jnp.int32), ranks.astype(jnp.int32), ds_, A)


# ---------------------------------------------------------------------------
# backward weight grads: dA = X^T @ dS ; dB = scale * S^T @ dY
# (rank is the OUTPUT columns/rows and is masked on load, so the padded
#  rank region of the gradients is exactly zero — no re-mask needed)
# ---------------------------------------------------------------------------

def _da_kernel(rows_ref, ranks_ref, x_ref, ds_ref, da_ref, acc_ref):
    z, t = pl.program_id(0), pl.program_id(2)
    vrow = rows_ref[z] - t * x_ref.shape[1]
    rank = ranks_ref[z]

    @pl.when(t == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)

    @pl.when((vrow > 0) & (rank > 0))
    def _acc():
        xm = _row_mask(x_ref[0], vrow)
        dsm = _col_mask(ds_ref[0], rank)
        acc_ref[...] += jax.lax.dot_general(xm, dsm, _TN,
                                            preferred_element_type=F32)

    @pl.when(t == pl.num_programs(2) - 1)
    def _done():
        da_ref[0] = acc_ref[...]


def da(x: jnp.ndarray, ds_: jnp.ndarray, rows: jnp.ndarray,
       ranks: jnp.ndarray, *, bd: int = K.BN, bt: int = K.BT,
       interpret: bool = False) -> jnp.ndarray:
    """x: [Z,T,din], ds: [Z,T,r] -> dA [Z,din,r] fp32; columns past
    ranks[z] stay exactly zero."""
    Z, T, din = x.shape
    r = ds_.shape[2]
    bd, bt = min(bd, din), min(bt, T)
    return pl.pallas_call(
        _da_kernel,
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=2,
            grid=(Z, din // bd, T // bt),
            in_specs=[
                pl.BlockSpec((1, bt, bd), lambda z, d, t, rr, rk: (z, t, d)),
                pl.BlockSpec((1, bt, r), lambda z, d, t, rr, rk: (z, t, 0)),
            ],
            out_specs=pl.BlockSpec((1, bd, r),
                                   lambda z, d, t, rr, rk: (z, d, 0)),
            scratch_shapes=[pltpu.VMEM((bd, r), F32)],
        ),
        out_shape=jax.ShapeDtypeStruct((Z, din, r), F32),
        interpret=interpret,
    )(rows.astype(jnp.int32), ranks.astype(jnp.int32), x, ds_)


def _db_kernel(scale_ref, rows_ref, ranks_ref, s_ref, dy_ref, db_ref,
               acc_ref):
    z, t = pl.program_id(0), pl.program_id(2)
    vrow = rows_ref[z] - t * s_ref.shape[1]
    rank = ranks_ref[z]

    @pl.when(t == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)

    @pl.when((vrow > 0) & (rank > 0))
    def _acc():
        sm = _col_mask(_row_mask(s_ref[0], vrow), rank)
        acc_ref[...] += jax.lax.dot_general(sm, dy_ref[0], _TN,
                                            preferred_element_type=F32)

    @pl.when(t == pl.num_programs(2) - 1)
    def _done():
        db_ref[0] = acc_ref[...] * scale_ref[z]


def db(s: jnp.ndarray, dy: jnp.ndarray, scale: jnp.ndarray,
       rows: jnp.ndarray, ranks: jnp.ndarray, *, bn: int = K.BN,
       bt: int = K.BT, interpret: bool = False) -> jnp.ndarray:
    """s: [Z,T,r], dy: [Z,T,dout] -> dB [Z,r,dout] fp32; rows past
    ranks[z] stay exactly zero."""
    Z, T, r = s.shape
    dout = dy.shape[2]
    bn, bt = min(bn, dout), min(bt, T)
    return pl.pallas_call(
        _db_kernel,
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=3,
            grid=(Z, dout // bn, T // bt),
            in_specs=[
                pl.BlockSpec((1, bt, r),
                             lambda z, n, t, sc, rr, rk: (z, t, 0)),
                pl.BlockSpec((1, bt, bn),
                             lambda z, n, t, sc, rr, rk: (z, t, n)),
            ],
            out_specs=pl.BlockSpec((1, r, bn),
                                   lambda z, n, t, sc, rr, rk: (z, 0, n)),
            scratch_shapes=[pltpu.VMEM((r, bn), F32)],
        ),
        out_shape=jax.ShapeDtypeStruct((Z, r, dout), F32),
        interpret=interpret,
    )(scale.astype(F32), rows.astype(jnp.int32), ranks.astype(jnp.int32),
      s, dy)
