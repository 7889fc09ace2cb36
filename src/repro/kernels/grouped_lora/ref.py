"""Pure-jnp oracle for the grouped multi-adapter LoRA kernels.

Shapes (slot-stacked, paper §A.1 rank-only padding):
    x:      [Z, T, d_in]
    A:      [Z, d_in, r]      (columns >= true rank are zero)
    B:      [Z, r, d_out]     (rows    >= true rank are zero)
    scale:  [Z]               (alpha / r; paper default alpha=2r => 2.0)
    y_base: [Z, T, d_out]     (frozen-backbone output for the fused add)
"""
from __future__ import annotations

from typing import Optional, Tuple

import jax.numpy as jnp


def grouped_xa_ref(x: jnp.ndarray, A: jnp.ndarray) -> jnp.ndarray:
    """S_i = X_i @ A_i, fp32 accumulation, result in x.dtype."""
    s = jnp.einsum("ztd,zdr->ztr", x, A,
                   preferred_element_type=jnp.float32)
    return s.astype(x.dtype)


def grouped_sb_add_ref(s: jnp.ndarray, B: jnp.ndarray, scale: jnp.ndarray,
                       y_base: Optional[jnp.ndarray] = None) -> jnp.ndarray:
    """Y = (S_i @ B_i) * scale_i (+ Y_base), fused epilogue add."""
    y = jnp.einsum("ztr,zro->zto", s, B,
                   preferred_element_type=jnp.float32)
    y = y * scale.astype(jnp.float32)[:, None, None]
    if y_base is not None:
        y = y + y_base.astype(jnp.float32)
    return y.astype(s.dtype)


def grouped_lora_ref(x, A, B, scale, y_base=None) -> jnp.ndarray:
    return grouped_sb_add_ref(grouped_xa_ref(x, A), B, scale, y_base)


def _rows_mask(x: jnp.ndarray, rows: jnp.ndarray) -> jnp.ndarray:
    """[Z,T,*] -> zero every token row t >= rows[z] of slot z's lane."""
    Z, T = x.shape[0], x.shape[1]
    keep = jnp.arange(T)[None, :] < rows[:, None]          # [Z, T]
    return x * keep[:, :, None].astype(x.dtype)


def ragged_lora_ref(x, A, B, scale, rows, y_base=None) -> jnp.ndarray:
    """Ragged oracle: slot z contributes only its first rows[z] token rows;
    padded rows produce a zero delta (y_base passes through)."""
    return grouped_lora_ref(_rows_mask(x, rows), A, B, scale, y_base)


def ragged_lora_bwd_ref(x, A, B, scale, rows, s, dy
                        ) -> Tuple[jnp.ndarray, jnp.ndarray, jnp.ndarray]:
    """Ragged backward oracle: padded rows receive zero dX and contribute
    nothing to dA/dB (mask dy; x/s pads already produce zero products)."""
    return grouped_lora_bwd_ref(_rows_mask(x, rows), A, B, scale,
                                _rows_mask(s, rows), _rows_mask(dy, rows))


def _ranks_mask_A(A: jnp.ndarray, ranks: jnp.ndarray) -> jnp.ndarray:
    """[Z,din,r] -> zero columns rr >= ranks[z] of slot z's A."""
    keep = jnp.arange(A.shape[2])[None, :] < ranks[:, None]    # [Z, r]
    return jnp.where(keep[:, None, :], A, jnp.zeros((), A.dtype))


def _ranks_mask_B(B: jnp.ndarray, ranks: jnp.ndarray) -> jnp.ndarray:
    """[Z,r,dout] -> zero rows rr >= ranks[z] of slot z's B."""
    keep = jnp.arange(B.shape[1])[None, :] < ranks[:, None]    # [Z, r]
    return jnp.where(keep[:, :, None], B, jnp.zeros((), B.dtype))


def ranklocal_lora_ref(x, A, B, scale, ranks, rows=None,
                       y_base=None) -> jnp.ndarray:
    """Rank-local oracle: slot z uses only its first ranks[z] rank columns
    of A / rank rows of B (and, when ``rows`` is given, only its first
    rows[z] token rows). The padded rank region contributes nothing even
    when it holds garbage."""
    if rows is not None:
        x = _rows_mask(x, rows)
    return grouped_lora_ref(x, _ranks_mask_A(A, ranks),
                            _ranks_mask_B(B, ranks), scale, y_base)


def ranklocal_lora_bwd_ref(x, A, B, scale, ranks, rows, s, dy
                           ) -> Tuple[jnp.ndarray, jnp.ndarray, jnp.ndarray]:
    """Rank-local backward oracle: the padded rank region of dA/dB is
    exactly zero (masked on load, never accumulated) and
    padded token rows receive zero dX."""
    if rows is not None:
        x = _rows_mask(x, rows)
        s = _rows_mask(s, rows)
        dy = _rows_mask(dy, rows)
    Am, Bm = _ranks_mask_A(A, ranks), _ranks_mask_B(B, ranks)
    dx, dA, dB = grouped_lora_bwd_ref(x, Am, Bm, scale,
                                      _ranks_mask_A(s, ranks), dy)
    # dA cols / dB rows beyond the true rank never accumulate
    return dx, _ranks_mask_A(dA, ranks), _ranks_mask_B(dB, ranks)


def grouped_lora_bwd_ref(x, A, B, scale, s, dy
                         ) -> Tuple[jnp.ndarray, jnp.ndarray, jnp.ndarray]:
    """(dX, dA, dB) for Y = scale * (X A) B [+ Y_base].

    dS = scale * dY B^T ; dX = dS A^T ; dA = X^T dS ; dB = scale * S^T dY.
    Weight grads in fp32 (optimizer master dtype), dX in x.dtype.
    """
    dyf = dy.astype(jnp.float32)
    sc = scale.astype(jnp.float32)[:, None, None]
    ds = jnp.einsum("zto,zro->ztr", dyf * sc, B.astype(jnp.float32))
    dx = jnp.einsum("ztr,zdr->ztd", ds, A.astype(jnp.float32))
    dA = jnp.einsum("ztd,ztr->zdr", x.astype(jnp.float32), ds)
    dB = jnp.einsum("ztr,zto->zro", s.astype(jnp.float32), dyf * sc)
    return dx.astype(x.dtype), dA, dB
