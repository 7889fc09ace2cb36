"""Differentiable jit wrapper over the grouped-LoRA Pallas kernels.

``grouped_lora(x, A, B, scale, y_base=None)`` == scale*(x@A)@B (+ y_base),
grouped over the leading slot axis, with a custom VJP that reuses the
paper's backward schedule (dS/dX/dA/dB grouped kernels, forward caches S —
paper §6.1 "the forward caches intermediate S to avoid recomputation").

The wrapper pads T / d_in / d_out / r up to tile multiples (zero padding is
exact for every kernel: padded rows/cols of x/A/B are zero and padded
outputs are sliced away) so arbitrary shapes hit the fixed-tile kernels.
"""
from __future__ import annotations

import functools
from typing import Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from repro.kernels.grouped_lora import grouped_lora as K
from repro.kernels.grouped_lora import ragged as R
from repro.kernels.grouped_lora import ranklocal as RL
from repro.kernels.grouped_lora.autotune import DEFAULT_PLAN, TilePlan

_LANE = 128   # TPU lane width; last-dim tile multiple
_SUB = 8      # sublane multiple


def _ceil_to(x: int, m: int) -> int:
    return -(-x // m) * m


def _pad_axis(x: jnp.ndarray, axis: int, to: int) -> jnp.ndarray:
    pad = to - x.shape[axis]
    if pad == 0:
        return x
    widths = [(0, 0)] * x.ndim
    widths[axis] = (0, pad)
    return jnp.pad(x, widths)


# cached: the plan is pure shape arithmetic, but every trace of every
# variant recomputes it (fwd + 4-kernel bwd per call site) — repeated
# same-shape calls (one per LoRA target per layer per step) hit the cache
@functools.lru_cache(maxsize=None)
def _tile_plan(T: int, din: int, dout: int, r: int
               ) -> Tuple[int, int, int, int]:
    Tp = _ceil_to(T, min(K.BM, _ceil_to(T, _SUB)))
    Tp = _ceil_to(Tp, _SUB)
    dinp = _ceil_to(din, min(K.BK, _ceil_to(din, _LANE)))
    doutp = _ceil_to(dout, min(K.BN, _ceil_to(dout, _LANE)))
    rp = _ceil_to(r, _SUB)
    return Tp, dinp, doutp, rp


# ---------------------------------------------------------------------------
# core padded implementations (not differentiable; used by fwd/bwd rules)
# ---------------------------------------------------------------------------

def _pad_fwd(x, A, B, y_base):
    """Pad (x, A, B, y_base) to the cached tile plan — shared by the
    dense/ragged/rank-local forward impls (they differ only in which
    kernel set consumes the padded operands)."""
    T, din = x.shape[1], x.shape[2]
    r, dout = B.shape[1], B.shape[2]
    Tp, dinp, doutp, rp = _tile_plan(T, din, dout, r)
    xp = _pad_axis(_pad_axis(x, 1, Tp), 2, dinp)
    Ap = _pad_axis(_pad_axis(A, 1, dinp), 2, rp).astype(x.dtype)
    Bp = _pad_axis(_pad_axis(B, 1, rp), 2, doutp).astype(x.dtype)
    yb = None
    if y_base is not None:
        yb = _pad_axis(_pad_axis(y_base, 1, Tp), 2, doutp)
    return xp, Ap, Bp, yb


def _pad_bwd(x, A, B, s, dy):
    """Pad the backward operands (residual s is padded on r already)."""
    xp, Ap, Bp, _ = _pad_fwd(x, A, B, None)
    sp = _pad_axis(s, 1, xp.shape[1])
    dyp = _pad_axis(_pad_axis(dy, 1, xp.shape[1]), 2,
                    Bp.shape[2]).astype(x.dtype)
    return xp, Ap, Bp, sp, dyp


def _fwd_impl(x, A, B, scale, y_base, interpret, plan=DEFAULT_PLAN):
    T, dout = x.shape[1], B.shape[2]
    xp, Ap, Bp, yb = _pad_fwd(x, A, B, y_base)
    s = K.xa(xp, Ap, bm=plan.bm, bk=plan.bk, interpret=interpret)
    y = K.sb_add(s, Bp, scale, yb, bm=plan.bm, bn=plan.bn,
                 interpret=interpret)
    return y[:, :T, :dout], s[:, :T, :]      # s padded on r only


def _bwd_impl(x, A, B, scale, s, dy, interpret, plan=DEFAULT_PLAN):
    T, din = x.shape[1], x.shape[2]
    r, dout = B.shape[1], B.shape[2]
    xp, Ap, Bp, sp, dyp = _pad_bwd(x, A, B, s, dy)
    ds_ = K.ds(dyp, Bp, scale, bm=plan.bm, bk=plan.bk, interpret=interpret)
    dx_ = K.dx(ds_, Ap, bm=plan.bm, bn=plan.bn, interpret=interpret)
    dA_ = K.da(xp, ds_, bd=plan.bn, bt=plan.bt, interpret=interpret)
    dB_ = K.db(sp, dyp, scale, bn=plan.bn, bt=plan.bt, interpret=interpret)
    return (dx_[:, :T, :din], dA_[:, :din, :r], dB_[:, :r, :dout])


# ---------------------------------------------------------------------------
# custom_vjp variants (cached per (interpret, has_base, plan) — TilePlan is
# frozen/hashable, so tuned plans get their own traced variant and the
# default plan keeps hitting the original cache entries)
# ---------------------------------------------------------------------------

@functools.lru_cache(maxsize=None)
def _make_fn(interpret: bool, has_base: bool, plan: TilePlan = DEFAULT_PLAN):
    if has_base:
        @jax.custom_vjp
        def f(x, A, B, scale, y_base):
            y, _ = _fwd_impl(x, A, B, scale, y_base, interpret, plan)
            return y

        def f_fwd(x, A, B, scale, y_base):
            y, s = _fwd_impl(x, A, B, scale, y_base, interpret, plan)
            return y, (x, A, B, scale, s)

        def f_bwd(res, dy):
            x, A, B, scale, s = res
            dx_, dA_, dB_ = _bwd_impl(x, A, B, scale, s, dy, interpret, plan)
            dscale = jnp.zeros_like(scale)   # scale is a hyperparam
            return dx_, dA_, dB_, dscale, dy

        f.defvjp(f_fwd, f_bwd)
        return f

    @jax.custom_vjp
    def g(x, A, B, scale):
        y, _ = _fwd_impl(x, A, B, scale, None, interpret, plan)
        return y

    def g_fwd(x, A, B, scale):
        y, s = _fwd_impl(x, A, B, scale, None, interpret, plan)
        return y, (x, A, B, scale, s)

    def g_bwd(res, dy):
        x, A, B, scale, s = res
        dx_, dA_, dB_ = _bwd_impl(x, A, B, scale, s, dy, interpret, plan)
        return dx_, dA_, dB_, jnp.zeros_like(scale)

    g.defvjp(g_fwd, g_bwd)
    return g


def grouped_lora(x: jnp.ndarray, A: jnp.ndarray, B: jnp.ndarray,
                 scale: jnp.ndarray,
                 y_base: Optional[jnp.ndarray] = None, *,
                 interpret: bool = False,
                 plan: Optional[TilePlan] = None) -> jnp.ndarray:
    """Differentiable grouped LoRA: scale*(x@A)@B (+ y_base).

    x: [Z,T,din]; A: [Z,din,r]; B: [Z,r,dout]; scale: [Z].
    ``plan`` (an autotuned ``TilePlan``) overrides the static block
    constants; None keeps the defaults. Tuned plans re-tile only parallel
    grid dims, so outputs are bitwise identical to the default plan.
    """
    fn = _make_fn(bool(interpret), y_base is not None,
                  plan if plan is not None else DEFAULT_PLAN)
    if y_base is not None:
        return fn(x, A, B, scale, y_base)
    return fn(x, A, B, scale)


# ---------------------------------------------------------------------------
# ragged variant: per-slot token-row counts (heterogeneous batch widths)
# ---------------------------------------------------------------------------

def _ragged_fwd_impl(x, A, B, scale, rows, y_base, interpret,
                     plan=DEFAULT_PLAN):
    T, dout = x.shape[1], B.shape[2]
    xp, Ap, Bp, yb = _pad_fwd(x, A, B, y_base)
    s = R.xa(xp, Ap, rows, bm=plan.bm, bk=plan.bk, interpret=interpret)
    y = R.sb_add(s, Bp, scale, rows, yb, bm=plan.bm, bn=plan.bn,
                 interpret=interpret)
    return y[:, :T, :dout], s[:, :T, :]


def _ragged_bwd_impl(x, A, B, scale, rows, s, dy, interpret,
                     plan=DEFAULT_PLAN):
    T, din = x.shape[1], x.shape[2]
    r, dout = B.shape[1], B.shape[2]
    xp, Ap, Bp, sp, dyp = _pad_bwd(x, A, B, s, dy)
    ds_ = R.ds(dyp, Bp, scale, rows, bm=plan.bm, bk=plan.bk,
               interpret=interpret)
    dx_ = R.dx(ds_, Ap, rows, bm=plan.bm, bn=plan.bn, interpret=interpret)
    dA_ = R.da(xp, ds_, rows, bd=plan.bn, bt=plan.bt, interpret=interpret)
    dB_ = R.db(sp, dyp, scale, rows, bn=plan.bn, bt=plan.bt,
               interpret=interpret)
    return (dx_[:, :T, :din], dA_[:, :din, :r], dB_[:, :r, :dout])


def _rows_cotangent(rows):
    # integer primal => float0 cotangent (rows carries no gradient)
    return np.zeros(np.shape(rows), jax.dtypes.float0)


@functools.lru_cache(maxsize=None)
def _make_ragged_fn(interpret: bool, has_base: bool,
                    plan: TilePlan = DEFAULT_PLAN):
    if has_base:
        @jax.custom_vjp
        def f(x, A, B, scale, rows, y_base):
            y, _ = _ragged_fwd_impl(x, A, B, scale, rows, y_base, interpret,
                                    plan)
            return y

        def f_fwd(x, A, B, scale, rows, y_base):
            y, s = _ragged_fwd_impl(x, A, B, scale, rows, y_base, interpret,
                                    plan)
            return y, (x, A, B, scale, rows, s)

        def f_bwd(res, dy):
            x, A, B, scale, rows, s = res
            dx_, dA_, dB_ = _ragged_bwd_impl(x, A, B, scale, rows, s, dy,
                                             interpret, plan)
            return (dx_, dA_, dB_, jnp.zeros_like(scale),
                    _rows_cotangent(rows), dy)

        f.defvjp(f_fwd, f_bwd)
        return f

    @jax.custom_vjp
    def g(x, A, B, scale, rows):
        y, _ = _ragged_fwd_impl(x, A, B, scale, rows, None, interpret, plan)
        return y

    def g_fwd(x, A, B, scale, rows):
        y, s = _ragged_fwd_impl(x, A, B, scale, rows, None, interpret, plan)
        return y, (x, A, B, scale, rows, s)

    def g_bwd(res, dy):
        x, A, B, scale, rows, s = res
        dx_, dA_, dB_ = _ragged_bwd_impl(x, A, B, scale, rows, s, dy,
                                         interpret, plan)
        return (dx_, dA_, dB_, jnp.zeros_like(scale),
                _rows_cotangent(rows))

    g.defvjp(g_fwd, g_bwd)
    return g


def ragged_grouped_lora(x: jnp.ndarray, A: jnp.ndarray, B: jnp.ndarray,
                        scale: jnp.ndarray, rows: jnp.ndarray,
                        y_base: Optional[jnp.ndarray] = None, *,
                        interpret: bool = False,
                        plan: Optional[TilePlan] = None) -> jnp.ndarray:
    """Differentiable RAGGED grouped LoRA: slot z applies its adapter to
    only the first ``rows[z]`` token rows of its lane; padded rows get a
    zero delta (y_base passes through) and zero gradients.

    x: [Z,T,din]; A: [Z,din,r]; B: [Z,r,dout]; scale: [Z]; rows: [Z] int.
    ``rows == T`` everywhere reproduces ``grouped_lora`` exactly — the
    executor dispatches dense for homogeneous mixes, ragged otherwise.
    ``plan`` overrides the static block constants (see ``grouped_lora``).
    """
    fn = _make_ragged_fn(bool(interpret), y_base is not None,
                         plan if plan is not None else DEFAULT_PLAN)
    if y_base is not None:
        return fn(x, A, B, scale, rows, y_base)
    return fn(x, A, B, scale, rows)


# ---------------------------------------------------------------------------
# rank-local variant: per-slot true ranks (composes with ragged rows)
# ---------------------------------------------------------------------------

def _ranklocal_fwd_impl(x, A, B, scale, ranks, rows, y_base, interpret,
                        plan=DEFAULT_PLAN):
    T, dout = x.shape[1], B.shape[2]
    xp, Ap, Bp, yb = _pad_fwd(x, A, B, y_base)
    s = RL.xa(xp, Ap, rows, ranks, bm=plan.bm, bk=plan.bk,
              interpret=interpret)
    y = RL.sb_add(s, Bp, scale, rows, ranks, yb, bm=plan.bm, bn=plan.bn,
                  interpret=interpret)
    return y[:, :T, :dout], s[:, :T, :]


def _ranklocal_bwd_impl(x, A, B, scale, ranks, rows, s, dy, interpret,
                        plan=DEFAULT_PLAN):
    T, din = x.shape[1], x.shape[2]
    r, dout = B.shape[1], B.shape[2]
    xp, Ap, Bp, sp, dyp = _pad_bwd(x, A, B, s, dy)
    ds_ = RL.ds(dyp, Bp, scale, rows, ranks, bm=plan.bm, bk=plan.bk,
                interpret=interpret)
    dx_ = RL.dx(ds_, Ap, rows, ranks, bm=plan.bm, bn=plan.bn,
                interpret=interpret)
    dA_ = RL.da(xp, ds_, rows, ranks, bd=plan.bn, bt=plan.bt,
                interpret=interpret)
    dB_ = RL.db(sp, dyp, scale, rows, ranks, bn=plan.bn, bt=plan.bt,
                interpret=interpret)
    return (dx_[:, :T, :din], dA_[:, :din, :r], dB_[:, :r, :dout])


@functools.lru_cache(maxsize=None)
def _make_ranklocal_fn(interpret: bool, has_base: bool,
                       plan: TilePlan = DEFAULT_PLAN):
    if has_base:
        @jax.custom_vjp
        def f(x, A, B, scale, ranks, rows, y_base):
            y, _ = _ranklocal_fwd_impl(x, A, B, scale, ranks, rows, y_base,
                                       interpret, plan)
            return y

        def f_fwd(x, A, B, scale, ranks, rows, y_base):
            y, s = _ranklocal_fwd_impl(x, A, B, scale, ranks, rows, y_base,
                                       interpret, plan)
            return y, (x, A, B, scale, ranks, rows, s)

        def f_bwd(res, dy):
            x, A, B, scale, ranks, rows, s = res
            dx_, dA_, dB_ = _ranklocal_bwd_impl(x, A, B, scale, ranks, rows,
                                                s, dy, interpret, plan)
            return (dx_, dA_, dB_, jnp.zeros_like(scale),
                    _rows_cotangent(ranks), _rows_cotangent(rows), dy)

        f.defvjp(f_fwd, f_bwd)
        return f

    @jax.custom_vjp
    def g(x, A, B, scale, ranks, rows):
        y, _ = _ranklocal_fwd_impl(x, A, B, scale, ranks, rows, None,
                                   interpret, plan)
        return y

    def g_fwd(x, A, B, scale, ranks, rows):
        y, s = _ranklocal_fwd_impl(x, A, B, scale, ranks, rows, None,
                                   interpret, plan)
        return y, (x, A, B, scale, ranks, rows, s)

    def g_bwd(res, dy):
        x, A, B, scale, ranks, rows, s = res
        dx_, dA_, dB_ = _ranklocal_bwd_impl(x, A, B, scale, ranks, rows,
                                            s, dy, interpret, plan)
        return (dx_, dA_, dB_, jnp.zeros_like(scale),
                _rows_cotangent(ranks), _rows_cotangent(rows))

    g.defvjp(g_fwd, g_bwd)
    return g


def _concrete_min(v) -> Optional[int]:
    """min(v) when v is host-known (numpy / concrete jax array), else
    None (tracer: the dispatch decision was made outside the trace)."""
    try:
        return int(jnp.min(jnp.asarray(v)))
    except jax.errors.ConcretizationTypeError:
        return None


def ranklocal_grouped_lora(x: jnp.ndarray, A: jnp.ndarray, B: jnp.ndarray,
                           scale: jnp.ndarray, ranks: jnp.ndarray,
                           rows: Optional[jnp.ndarray] = None,
                           y_base: Optional[jnp.ndarray] = None, *,
                           interpret: bool = False,
                           plan: Optional[TilePlan] = None) -> jnp.ndarray:
    """Differentiable RANK-LOCAL grouped LoRA: slot z applies only the
    first ``ranks[z]`` rank columns/rows of its adapter (and, with
    ``rows``, only its first rows[z] token rows). The kernels mask the
    padded rank on load: it gets a zero output and exactly zero
    gradient, so no post-step re-mask is needed on this path.

    x: [Z,T,din]; A: [Z,din,r]; B: [Z,r,dout]; scale/ranks/rows: [Z].
    Concrete ``ranks`` >= r everywhere dispatch to the dense/ragged path
    (identical tiling => bitwise-equal), mirroring the executor's per-step
    dense-vs-ragged dispatch. ``plan`` (an autotuned ``TilePlan``)
    overrides the static block constants on whichever path dispatch picks;
    tuned-vs-default outputs are bitwise identical (parallel-dim re-tiling
    only — the autotuner pins every contraction grouping).
    """
    r = A.shape[2]
    cmin = _concrete_min(ranks)
    if cmin is not None and cmin >= r:
        if rows is None:
            return grouped_lora(x, A, B, scale, y_base, interpret=interpret,
                                plan=plan)
        return ragged_grouped_lora(x, A, B, scale, rows, y_base,
                                   interpret=interpret, plan=plan)
    if rows is None:
        rows = jnp.full((x.shape[0],), x.shape[1], jnp.int32)
    fn = _make_ranklocal_fn(bool(interpret), y_base is not None,
                            plan if plan is not None else DEFAULT_PLAN)
    if y_base is not None:
        return fn(x, A, B, scale, ranks, rows, y_base)
    return fn(x, A, B, scale, ranks, rows)
