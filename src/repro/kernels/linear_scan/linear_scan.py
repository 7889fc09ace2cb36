"""Pallas TPU kernel: chunked gated linear scan (RWKV6 / Mamba-SSD core).

The §Perf analysis showed the jnp chunked scan's dominant HBM term is the
exact-log-space pair tensor exp(L_t - L_i) k q of shape [C, C, K]
materialized per chunk. This kernel keeps that tensor (built 16 query rows
at a time) and all chunk intermediates VMEM-resident, and takes the
cumulative log-decays as triangular fp32 matmuls (Mosaic lowers no
cumsum): per grid step, HBM moves only the q/k/v/logw
chunk tiles and the y output tile — bytes drop from O(S·C·K) extra per row
to the O(S·(3K+V)) I/O floor.

Layout: fused batch rows B = Z*b*H. Grid (B, S/C) — the TPU grid iterates
the LAST dimension fastest and sequentially, so the recurrent state lives
in a VMEM scratch carried across chunk steps of the same row (initialized
at chunk==0 from the initial-state tile, written out at the last chunk).

The recurrence (decay_on_query False => RWKV with bonus u; True => SSD):
    S_c   = diag(exp(L_C)) S_{c-1} + (k . exp(L_C - L))^T v
    y     = (q . exp(Lq)) S_{c-1} + P v,   P_ti = sum_K q_t k_i e^{Lq_t-L_i}
All math fp32 in VMEM; pair exponents are differences of cumulative
log-decays => no overflow for arbitrarily strong decay (same numerics as
the jnp core, validated against it in interpret mode).
"""
from __future__ import annotations

import functools
import math
from typing import Optional, Tuple

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

F32 = jnp.float32
NEG_INF = -1e30
_EXACT = jax.lax.Precision.HIGHEST     # fp32 passes on the MXU
_TN = (((0,), (0,)), ((), ()))         # a.T @ b
_PAIR_ROWS = 16                        # query rows per pair-tensor block


def _kernel(q_ref, k_ref, v_ref, lw_ref, u_ref, s0_ref,
            y_ref, sout_ref, state, *, decay_on_query: bool,
            use_bonus: bool):
    c = pl.program_id(1)
    C, K = q_ref.shape[1], q_ref.shape[2]

    @pl.when(c == 0)
    def _init():
        state[...] = s0_ref[0]

    q = q_ref[0].astype(F32)
    k = k_ref[0].astype(F32)
    v = v_ref[0].astype(F32)
    lw = lw_ref[0].astype(F32)

    # cumulative log-decays as exact-fp32 matmuls with a triangle of ones
    # (Mosaic has no cumsum): L inclusive [C,K] <= 0, L_end^T [K,1]
    t = jax.lax.broadcasted_iota(jnp.int32, (C, C), 0)
    i = jax.lax.broadcasted_iota(jnp.int32, (C, C), 1)
    L = jnp.dot((t >= i).astype(F32), lw, precision=_EXACT,
                preferred_element_type=F32)
    Lq = L if decay_on_query else L - lw          # exclusive for RWKV

    # ---- state contribution (MXU): (q . e^{Lq}) @ S_prev
    S_prev = state[...]
    q_scaled = q * jnp.exp(Lq)
    y = jnp.dot(q_scaled, S_prev, preferred_element_type=F32)

    if use_bonus:                                 # P[t,t] += sum_K q u k
        y = y + jnp.sum(q * u_ref[0].astype(F32) * k, axis=-1,
                        keepdims=True) * v

    # ---- intra-chunk pairs, exact log-space, fully VMEM-resident: the
    # [BQ,C,K] pair tensor of BQ query rows at a time stays a few MiB
    bq = math.gcd(C, _PAIR_ROWS)
    tq = jax.lax.broadcasted_iota(jnp.int32, (bq, C, K), 0)
    ik = jax.lax.broadcasted_iota(jnp.int32, (bq, C, K), 1)
    for r0 in range(0, C, bq):
        visible = (tq + r0 >= ik) if decay_on_query else (tq + r0 > ik)
        dd = Lq[r0:r0 + bq][:, None, :] - L[None, :, :]
        dd = jnp.where(visible, dd, NEG_INF)
        P = jnp.sum(q[r0:r0 + bq][:, None, :] * k[None, :, :]
                    * jnp.exp(dd), axis=-1)       # [BQ,C]
        y_rows = y[r0:r0 + bq] + jnp.dot(P, v, preferred_element_type=F32)
        y_ref[0, r0:r0 + bq, :] = y_rows.astype(y_ref.dtype)

    # ---- state update
    L_end = jnp.sum(lw, axis=0, keepdims=True)    # [1,K]
    L_end_col = jax.lax.dot_general(              # the same sums as [K,1]
        lw, jnp.ones((C, 1), F32), _TN, precision=_EXACT,
        preferred_element_type=F32)
    k_scaled = k * jnp.exp(L_end - L)
    new_state = (S_prev * jnp.exp(L_end_col)
                 + jax.lax.dot_general(k_scaled, v, _TN,
                                       preferred_element_type=F32))
    state[...] = new_state

    @pl.when(c == pl.num_programs(1) - 1)
    def _done():
        sout_ref[0] = new_state


def linear_scan(q: jnp.ndarray, k: jnp.ndarray, v: jnp.ndarray,
                logw: jnp.ndarray, *,
                bonus: Optional[jnp.ndarray] = None,
                decay_on_query: bool = False,
                initial_state: Optional[jnp.ndarray] = None,
                chunk: int = 32, interpret: bool = False
                ) -> Tuple[jnp.ndarray, jnp.ndarray]:
    """q,k,logw: [B,S,K]; v: [B,S,V]; bonus: [B,K]|None;
    initial_state: [B,K,V] fp32|None. Returns (y [B,S,V], state [B,K,V])."""
    B, S, K = q.shape
    V = v.shape[-1]
    C = min(chunk, S)
    assert S % C == 0, (S, C)
    n = S // C
    if initial_state is None:
        initial_state = jnp.zeros((B, K, V), F32)
    use_bonus = bonus is not None
    if bonus is None:
        bonus = jnp.zeros((B, K), F32)
    # [B,1,K] so the (1,1,K) block spans the last two array dims (Mosaic
    # rejects a (1,K) block over [B,K]: its sublane dim is neither 8-aligned
    # nor the whole axis)
    bonus = bonus.reshape(B, 1, K)

    kern = functools.partial(_kernel, decay_on_query=decay_on_query,
                             use_bonus=use_bonus)
    y, state = pl.pallas_call(
        kern,
        grid=(B, n),
        in_specs=[
            pl.BlockSpec((1, C, K), lambda b, c: (b, c, 0)),   # q
            pl.BlockSpec((1, C, K), lambda b, c: (b, c, 0)),   # k
            pl.BlockSpec((1, C, V), lambda b, c: (b, c, 0)),   # v
            pl.BlockSpec((1, C, K), lambda b, c: (b, c, 0)),   # logw
            pl.BlockSpec((1, 1, K), lambda b, c: (b, 0, 0)),   # bonus
            pl.BlockSpec((1, K, V), lambda b, c: (b, 0, 0)),   # state0
        ],
        out_specs=[
            pl.BlockSpec((1, C, V), lambda b, c: (b, c, 0)),   # y
            pl.BlockSpec((1, K, V), lambda b, c: (b, 0, 0)),   # state out
        ],
        out_shape=[
            jax.ShapeDtypeStruct((B, S, V), q.dtype),
            jax.ShapeDtypeStruct((B, K, V), F32),
        ],
        scratch_shapes=[pltpu.VMEM((K, V), F32)],
        interpret=interpret,
    )(q, k, v, logw, bonus, initial_state.astype(F32))
    return y, state
