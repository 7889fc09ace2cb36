"""Multi-adapter (slot-stacked) LoRA: the ALTO workload unit.

All adapters for one executor live in slot-stacked tensors with a leading
``Z`` axis (paper §A.1 rank-only padding):

    A: [Z, d_in, r_max]     B: [Z, r_max, d_out]

Per-slot true ranks are expressed by zeroing columns/rows beyond ``r_i``
(``rank_mask``); the padded region provably contributes zero to the output
and receives zero gradient (B's padded rows are zero ⇒ dS pads are zero ⇒
dA pads are zero), and the optimizer additionally re-masks after each update.
Under a ``slot_ranks`` binding the ranks become a COMPUTE dimension instead:
the rank-local grouped-GEMM kernels mask each slot's padded rank on load
and the re-mask is provably redundant (the padded region's gradient is exactly zero
by construction, not by cancellation).

``lora_delta`` dispatches between the pure-jnp path (the mathematical
reference; used under pjit/GSPMD where XLA fuses it) and the Pallas grouped
kernel (``repro.kernels.grouped_lora``) — the paper's fused grouped GEMM,
validated in interpret mode on CPU and targeted at TPU VMEM/MXU.
"""
from __future__ import annotations

import contextlib
import functools
import threading
from typing import Dict, Optional, Tuple

import jax
import jax.numpy as jnp

from repro.configs.base import ModelConfig

_backend = threading.local()

BACKENDS = ("jnp", "pallas", "pallas_interpret")


def set_backend(name: str) -> None:
    assert name in BACKENDS, name
    _backend.name = name


def get_backend() -> str:
    return getattr(_backend, "name", "jnp")


@contextlib.contextmanager
def backend(name: str):
    prev = get_backend()
    set_backend(name)
    try:
        yield
    finally:
        set_backend(prev)


# ---------------------------------------------------------------------------
# Ragged slot widths (heterogeneous per-adapter batch sizes)
# ---------------------------------------------------------------------------
#
# When co-located adapters train with different batch widths, slot z only
# owns the first ``rows[z]`` token rows of its [T = b_max*seq] lane.
# ``ragged_rows`` binds the per-slot row counts for the duration of a trace
# (the executor's fused train step sets it from the batch it packed); every
# ``lora_delta`` inside the trace then masks/skips the padded rows — the
# jnp path by zeroing them, the Pallas path via the ragged grouped-GEMM
# kernels that skip dead tiles outright.

@contextlib.contextmanager
def ragged_rows(rows: Optional[jnp.ndarray]):
    """Bind per-slot valid token-row counts ([Z] int32, in flattened
    lead-dims units) for lora_delta calls traced under this context."""
    prev = getattr(_backend, "rows", None)
    _backend.rows = rows
    try:
        yield
    finally:
        _backend.rows = prev


def get_ragged_rows() -> Optional[jnp.ndarray]:
    return getattr(_backend, "rows", None)


def _apply_row_mask(x: jnp.ndarray, rows: jnp.ndarray) -> jnp.ndarray:
    """Zero token rows >= rows[z]; row index runs over the flattened
    non-feature lead dims (b*seq for [Z, b, S, d] activations)."""
    Z = x.shape[0]
    n = 1
    for d in x.shape[1:-1]:
        n *= d
    idx = jnp.arange(n).reshape((1,) + x.shape[1:-1])
    keep = idx < rows.reshape((Z,) + (1,) * (x.ndim - 2))
    return jnp.where(keep[..., None], x, jnp.zeros((), x.dtype))


# ---------------------------------------------------------------------------
# Rank-local slot ranks (per-slot true-rank compute)
# ---------------------------------------------------------------------------
#
# Rank heterogeneity was historically pure zero-masking: every slot padded
# to r_max, so a rank-4 adapter co-located with a rank-64 one paid 16x its
# true FLOPs in every grouped GEMM. ``slot_ranks`` binds the per-slot TRUE
# ranks for the duration of a trace (the executor's fused step sets it
# from SlotManager state whenever a resident slot's rank is below r_max);
# every ``lora_delta`` inside the trace then confines slot z's compute to
# its first ranks[z] rank rows/columns — the jnp path by masking A/B (so
# correctness never leans on the padded region being zero), the Pallas
# path via the rank-local grouped-GEMM kernels, which mask each slot's
# padded rank on load. Composes with ``ragged_rows``.

@contextlib.contextmanager
def slot_ranks(ranks: Optional[jnp.ndarray]):
    """Bind per-slot true ranks ([Z] int32) for lora_delta calls traced
    under this context."""
    prev = getattr(_backend, "ranks", None)
    _backend.ranks = ranks
    try:
        yield
    finally:
        _backend.ranks = prev


def get_slot_ranks() -> Optional[jnp.ndarray]:
    return getattr(_backend, "ranks", None)


def _apply_rank_masks(A: jnp.ndarray, B: jnp.ndarray, ranks: jnp.ndarray
                      ) -> Tuple[jnp.ndarray, jnp.ndarray]:
    """Zero A's columns / B's rows at indices >= ranks[z]. For a
    full-rank slot the select is the identity, which keeps fused-vs-solo
    loss histories bitwise equal across the bind/no-bind dispatch."""
    keep = jnp.arange(A.shape[-1])[None, :] < ranks[:, None]     # [Z, r]
    Am = jnp.where(keep[:, None, :], A, jnp.zeros((), A.dtype))
    Bm = jnp.where(keep[:, :, None], B, jnp.zeros((), B.dtype))
    return Am, Bm


# ---------------------------------------------------------------------------
# Application
# ---------------------------------------------------------------------------

def lora_delta(x: jnp.ndarray, A: jnp.ndarray, B: jnp.ndarray,
               scale: jnp.ndarray | float) -> jnp.ndarray:
    """scale * (x @ A) @ B, grouped over the leading slot axis.

    x: [Z, ..., d_in]; A: [Z, d_in, r]; B: [Z, r, d_out]; scale: [] or [Z].
    Under a ``ragged_rows`` binding, slot z's delta is computed over only
    its first rows[z] token rows (zero delta + zero grads on the pad).
    """
    name = get_backend()
    rows = get_ragged_rows()
    ranks = get_slot_ranks()
    if name == "jnp":
        if rows is not None:
            x = _apply_row_mask(x, rows)
        if ranks is not None:
            A, B = _apply_rank_masks(A, B, ranks)
        return _lora_delta_jnp(x, A, B, scale)
    from repro.kernels.grouped_lora import ops as kops
    lead = x.shape[:-1]
    Z = x.shape[0]
    xt = x.reshape(Z, -1, x.shape[-1])
    interpret = (name == "pallas_interpret")
    if ranks is not None:
        y = kops.ranklocal_grouped_lora(
            xt, A, B, _scale_vec(scale, Z, x.dtype), ranks, rows=rows,
            interpret=interpret)
    elif rows is not None:
        y = kops.ragged_grouped_lora(xt, A, B, _scale_vec(scale, Z, x.dtype),
                                     rows, interpret=interpret)
    else:
        y = kops.grouped_lora(xt, A, B, _scale_vec(scale, Z, x.dtype),
                              interpret=interpret)
    return y.reshape(*lead, B.shape[-1])


def _scale_vec(scale, Z: int, dtype) -> jnp.ndarray:
    s = jnp.asarray(scale, jnp.float32)
    if s.ndim == 0:
        s = jnp.broadcast_to(s, (Z,))
    return s


def _lora_delta_jnp(x, A, B, scale):
    dt = x.dtype
    s = jnp.einsum("z...d,zdr->z...r", x, A.astype(dt))
    y = jnp.einsum("z...r,zro->z...o", s, B.astype(dt))
    sv = _scale_vec(scale, x.shape[0], dt)
    sv = sv.reshape((x.shape[0],) + (1,) * (y.ndim - 1))
    return y * sv.astype(dt)


def proj(x: jnp.ndarray, W: jnp.ndarray,
         lora_pair: Optional[Tuple[jnp.ndarray, jnp.ndarray]] = None,
         scale: jnp.ndarray | float = 2.0,
         name: Optional[str] = None) -> jnp.ndarray:
    """Frozen base projection + optional grouped LoRA residual.

    x: [Z, ..., d_in]; W: [d_in, d_out] (frozen, slot-shared). ``name``
    lets the sharding policy gather the ZeRO-sharded frozen weight over the
    adapter ("data") axis before use — the paper's Fig. 8 FSDP all-gather,
    instead of GSPMD's default activation-psum (§Perf opt_level >= 1).
    """
    from repro.models.shardctx import constrain
    if name is not None:
        W = constrain(W, f"weight:{name}")
    y = jnp.einsum("z...d,do->z...o", x, W)
    if lora_pair is not None:
        A, B = lora_pair
        y = y + lora_delta(x, A, B, scale)
    return y


# ---------------------------------------------------------------------------
# Initialization / masking
# ---------------------------------------------------------------------------

def rank_mask(ranks: jnp.ndarray, r_max: int) -> jnp.ndarray:
    """[Z] int ranks -> [Z, r_max] float {0,1} mask."""
    return (jnp.arange(r_max)[None, :] < ranks[:, None]).astype(jnp.float32)


def init_slot_lora(key: jax.Array, d_in: int, d_out: int, r_max: int, Z: int,
                   ranks: jnp.ndarray) -> Tuple[jnp.ndarray, jnp.ndarray]:
    """LoRA init: A ~ N(0, 1/r_max) (rank-masked), B = 0. fp32 master.

    The barrier keeps XLA from folding the scale into the normal's own
    constants inside a larger program, which would round A differently
    from the eager init (for an r_max whose scale is not a power of 2)."""
    A = jax.lax.optimization_barrier(
        jax.random.normal(key, (Z, d_in, r_max), jnp.float32))
    A = A * (r_max ** -0.5) * rank_mask(ranks, r_max)[:, None, :]
    B = jnp.zeros((Z, r_max, d_out), jnp.float32)
    return A, B


def init_lora_tree(key: jax.Array, cfg: ModelConfig, Z: int,
                   ranks: jnp.ndarray,
                   target_shapes: Dict[str, Tuple[int, int]],
                   num_layers: Optional[int] = None) -> Dict:
    """Stacked-over-layers LoRA tree: {target: {"A": [L,Z,din,r], "B": ...}}.

    Only targets present in ``target_shapes`` AND ``cfg.lora.targets`` get
    adapters (paper: all attention + MLP projections; per-family sets differ).
    Layer l of the i-th target draws from split key ``i * L + l``; one
    ``vmap`` over a target's L layer keys gives the same bits as
    ``init_slot_lora`` layer by layer, in one op per target instead of L.
    Traceable with ``ranks`` traced (``SlotManager``'s admission program).
    """
    L = num_layers if num_layers is not None else cfg.num_layers
    r = cfg.lora.r_max
    tree: Dict[str, Dict[str, jnp.ndarray]] = {}
    targets = [t for t in cfg.lora.targets if t in target_shapes]
    keys = jax.random.split(key, max(len(targets) * L, 1))
    for i, t in enumerate(targets):
        d_in, d_out = target_shapes[t]
        A, B = jax.vmap(functools.partial(
            init_slot_lora, d_in=d_in, d_out=d_out, r_max=r, Z=Z,
            ranks=ranks))(keys[i * L:(i + 1) * L])
        tree[t] = {"A": A, "B": B}
    return tree


def mask_lora_tree(tree: Dict, ranks: jnp.ndarray, r_max: int) -> Dict:
    """Re-apply rank masks to a stacked LoRA tree (post-optimizer-step)."""
    m = rank_mask(ranks, r_max)  # [Z, r]

    def mask_leaf(path_is_A: bool, x: jnp.ndarray) -> jnp.ndarray:
        if path_is_A:   # [L, Z, d_in, r]
            return x * m[None, :, None, :]
        return x * m[None, :, :, None]   # B: [L, Z, r, d_out]

    return {t: {"A": mask_leaf(True, ab["A"]), "B": mask_leaf(False, ab["B"])}
            for t, ab in tree.items()}


def slot_update(tree: Dict, slot: int, new_tree_slot: Dict) -> Dict:
    """Functionally replace one slot's adapter params (early-exit swap-in)."""
    def upd(old: jnp.ndarray, new: jnp.ndarray) -> jnp.ndarray:
        return old.at[:, slot].set(new)
    return jax.tree_util.tree_map(upd, tree, new_tree_slot)


def gather_slots(tree: Dict, slots: "list[int]") -> Dict:
    """Extract a sub-tree of the given slots (leading Z axis becomes
    len(slots)). Used to address one TASK's adapters inside a shared
    multi-task executor — slots co-located on one backbone need not be
    contiguous."""
    import numpy as _np
    idx = _np.asarray(slots, _np.int32)
    return jax.tree_util.tree_map(lambda x: x[:, idx], tree)


def zero_slot(tree: Dict, slot: int) -> Dict:
    """Zero a slot's adapter params (eviction)."""
    def z(x: jnp.ndarray) -> jnp.ndarray:
        return x.at[:, slot].set(0.0)
    return jax.tree_util.tree_map(z, tree)
