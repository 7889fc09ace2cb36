"""Unified multi-adapter decoder: init / forward / prefill / decode.

All entry points are pure functions of (cfg, params, lora, inputs) and are
safe under ``jax.eval_shape`` (the multi-pod dry-run lowers them with
ShapeDtypeStructs only). Layers are stacked on a leading L axis and executed
with ``lax.scan`` (+ per-layer remat in training) so HLO size and compile
time stay bounded for 80-layer configs.
"""
from __future__ import annotations

from typing import Any, Dict, Optional, Tuple

import jax
import jax.numpy as jnp

from repro.configs.base import ATTN_NONE, ATTN_SLIDING, ModelConfig
from repro.models import blocks as B
from repro.models.common import dtype_of, he_init, normal_init, rms_norm
from repro.models.mamba import mamba_dims
from repro.models.rope import rope_angles, text_positions
from repro.models.shardctx import constrain

RING_INIT_POS = -(1 << 30)    # ring-cache slots start far in the past


# ---------------------------------------------------------------------------
# Init
# ---------------------------------------------------------------------------

def init_params(key: jax.Array, cfg: ModelConfig) -> Dict:
    """Frozen backbone params, layers stacked on a leading L axis. The
    layer init is vmapped over the layer keys (bitwise equal to one init
    per key), so the device never holds the per-layer trees beside their
    stack — 2x the backbone at published widths."""
    dtype = dtype_of(cfg.dtype)
    k_emb, k_layers, k_head = jax.random.split(key, 3)
    layer_keys = jax.random.split(k_layers, cfg.num_layers)
    stacked = jax.vmap(lambda k: B.init_layer_params(k, cfg, dtype))(
        layer_keys)
    params = {
        "embed": normal_init(k_emb, (cfg.vocab_size, cfg.d_model),
                             0.02, dtype),
        "layers": stacked,
        "final_norm": jnp.ones((cfg.d_model,), jnp.float32),
    }
    if not cfg.tie_embeddings:
        params["lm_head"] = he_init(
            k_head, (cfg.d_model, cfg.vocab_size), cfg.d_model, dtype)
    return params


def target_shapes(cfg: ModelConfig) -> Dict[str, Tuple[int, int]]:
    return B.target_shapes(cfg)


# ---------------------------------------------------------------------------
# Shared pieces
# ---------------------------------------------------------------------------

def _train_window(cfg: ModelConfig) -> int:
    return cfg.sliding_window if cfg.attn_kind == ATTN_SLIDING else 0


def _embed(cfg: ModelConfig, params: Dict, tokens: jnp.ndarray,
           modal_embeds: Optional[jnp.ndarray]) -> jnp.ndarray:
    x = params["embed"][tokens]                      # [Z,b,S,d]
    if modal_embeds is not None:
        P = modal_embeds.shape[2]
        x = jax.lax.dynamic_update_slice_in_dim(
            x, modal_embeds.astype(x.dtype), 0, axis=2)
    return constrain(x, "residual")


def _angles(cfg: ModelConfig, positions: jnp.ndarray) -> Optional[jnp.ndarray]:
    if cfg.attn_kind == ATTN_NONE:
        return None
    return rope_angles(positions, cfg.resolved_head_dim, cfg.rope)


def _unembed(cfg: ModelConfig, params: Dict, x: jnp.ndarray) -> jnp.ndarray:
    W = params["lm_head"] if not cfg.tie_embeddings else params["embed"].T
    W = constrain(W, "weight:lm_head")
    logits = jnp.einsum("z...d,dv->z...v", x, W)
    return constrain(logits, "logits")


def _scan_layers(cfg: ModelConfig, x: jnp.ndarray, params: Dict, lora: Dict,
                 ctx: Dict, layer_states: Any = None, *, remat: bool,
                 need_state: bool) -> Tuple[jnp.ndarray, jnp.ndarray, Any]:
    """Scan the stacked layers. Returns (x, aux_sum, new_states|None)."""

    def body(carry, xs):
        base, lora_slice, state = xs
        c = dict(ctx)
        c["layer_state"] = state
        c["need_state"] = need_state
        xb, aux, new_state = B.apply_block(
            cfg, carry, {"base": base, "lora": lora_slice}, c)
        if not need_state:
            new_state = None
        return xb, (aux, new_state)

    if remat:
        body = jax.checkpoint(body, prevent_cse=False)

    L = cfg.num_layers
    if layer_states is None:
        layer_states = _none_states(L)
    xs = (params["layers"], _broadcast_lora(lora, L), layer_states)
    x, (auxs, new_states) = jax.lax.scan(body, x, xs)
    return x, jnp.sum(auxs), (new_states if need_state else None)


def _none_states(L: int):
    # a scan xs leaf of Nones: use a dummy zero array per layer
    return jnp.zeros((L,), jnp.int32)


def _broadcast_lora(lora: Dict, L: int) -> Dict:
    return lora if lora else {}


# layer_state of None is encoded by the dummy int array; blocks treat any
# non-dict layer_state as "no state".
def _decode_ctx_state(state):
    return state if isinstance(state, dict) else None


# ---------------------------------------------------------------------------
# Forward (train / prefill)
# ---------------------------------------------------------------------------

def forward(cfg: ModelConfig, params: Dict, lora: Dict, tokens: jnp.ndarray,
            *, positions: Optional[jnp.ndarray] = None,
            modal_embeds: Optional[jnp.ndarray] = None,
            cache: Optional[Dict] = None, remat: bool = True
            ) -> Tuple[jnp.ndarray, jnp.ndarray, Optional[Dict]]:
    """Full-sequence causal forward.

    tokens: [Z, b, S] int32. Returns (final_hidden [Z,b,S,d] (pre-unembed,
    post-final-norm), moe_aux scalar, new_cache or None).

    With ``cache`` given (prefill), per-layer K/V are written at index 0 and
    the filled cache is returned (decode can continue from it).
    """
    Z, b, S = tokens.shape
    x = _embed(cfg, params, tokens, modal_embeds)
    if positions is None:
        positions = text_positions((), S, cfg.rope)
    ctx: Dict[str, Any] = {
        "angles": _angles(cfg, positions),
        "q_pos": jnp.arange(S, dtype=jnp.int32),
        "window": _train_window(cfg),
    }
    layer_states = None
    need_state = cache is not None
    if cache is not None:
        ctx["write_index"] = jnp.array(0, jnp.int32)
        layer_states = cache["layers"]
        need_state = True
    x, aux, new_states = _scan_layers(
        cfg, x, params, lora, ctx, layer_states,
        remat=remat and cache is None, need_state=need_state)
    x = rms_norm(x, params["final_norm"], cfg.norm_eps)
    new_cache = None
    if cache is not None:
        per_lane = getattr(cache["pos"], "ndim", 0) == 2
        new_pos = (jnp.full_like(cache["pos"], S) if per_lane
                   else jnp.array(S, jnp.int32))
        new_cache = {"layers": new_states, "pos": new_pos}
        if "k_pos" in cache:
            kp = jnp.arange(cache["k_pos"].shape[-1], dtype=jnp.int32)
            new_cache["k_pos"] = (
                jnp.broadcast_to(kp, cache["k_pos"].shape) if per_lane
                else kp)
    return x, aux, new_cache


# ---------------------------------------------------------------------------
# Losses (chunked over sequence so [*, S, V] logits are never materialized)
# ---------------------------------------------------------------------------

def per_slot_xent(cfg: ModelConfig, params: Dict, hidden: jnp.ndarray,
                  labels: jnp.ndarray, chunk: int = 512
                  ) -> Tuple[jnp.ndarray, jnp.ndarray]:
    """hidden: [Z,b,S,d]; labels: [Z,b,S] int32 (-1 = ignore).

    Returns (sum_nll [Z] fp32, token_count [Z] fp32).
    """
    Z, b, S, d = hidden.shape
    W = (params["lm_head"] if not cfg.tie_embeddings
         else params["embed"].T)
    W = constrain(W, "weight:lm_head")
    c = min(chunk, S)
    while S % c:
        c -= 1
    n = S // c
    hs = jnp.moveaxis(hidden.reshape(Z, b, n, c, d), 2, 0)
    ls = jnp.moveaxis(labels.reshape(Z, b, n, c), 2, 0)

    def body(acc, xs):
        h, lab = xs
        logits = jnp.einsum("zbcd,dv->zbcv", h, W).astype(jnp.float32)
        logits = constrain(logits, "logits")
        lse = jax.nn.logsumexp(logits, axis=-1)
        gold = jnp.take_along_axis(
            logits, jnp.maximum(lab, 0)[..., None], axis=-1)[..., 0]
        mask = (lab >= 0).astype(jnp.float32)
        nll = (lse - gold) * mask
        s, cnt = acc
        return (s + jnp.sum(nll, axis=(1, 2)),
                cnt + jnp.sum(mask, axis=(1, 2))), None

    (s, cnt), _ = jax.lax.scan(
        body, (jnp.zeros((Z,), jnp.float32), jnp.zeros((Z,), jnp.float32)),
        (hs, ls))
    return s, cnt


# ---------------------------------------------------------------------------
# Decode
# ---------------------------------------------------------------------------

def init_cache(cfg: ModelConfig, Z: int, bsz: int, max_len: int, *,
               ring: bool = False, per_lane: bool = False) -> Dict:
    """Build a decode cache. ``ring=True`` => sliding-window ring buffer of
    size cfg.sliding_window (sub-quadratic long-context decode).

    ``per_lane=True`` => the decode position is a ``[Z, bsz]`` vector (and
    the ring ``k_pos`` a ``[Z, bsz, Sc]`` tensor): every (slot, lane)
    stream advances independently, so requests can join and leave
    mid-decode with no epoch barrier (true continuous batching)."""
    dtype = dtype_of(cfg.dtype)
    L = cfg.num_layers
    KV, hd = cfg.num_kv_heads, cfg.resolved_head_dim
    Sc = cfg.sliding_window if ring else max_len

    def attn_state():
        return {"k": jnp.zeros((L, Z, bsz, Sc, KV, hd), dtype),
                "v": jnp.zeros((L, Z, bsz, Sc, KV, hd), dtype)}

    if cfg.family == "ssm":
        H, hs = cfg.num_heads, cfg.ssm.head_size
        layers = {"wkv": jnp.zeros((L, Z, bsz, H, hs, hs), jnp.float32),
                  "tm_x": jnp.zeros((L, Z, bsz, cfg.d_model), dtype),
                  "cm_x": jnp.zeros((L, Z, bsz, cfg.d_model), dtype)}
    elif cfg.family == "hybrid":
        inner, H, hs = mamba_dims(cfg)
        layers = {
            "attn": attn_state(),
            "mamba": {
                "conv": jnp.zeros((L, Z, bsz, cfg.ssm.conv_width - 1, inner),
                                  jnp.float32),
                "ssm": jnp.zeros((L, Z, bsz, H, cfg.ssm.state_size, hs),
                                 jnp.float32),
            },
        }
    else:
        layers = {"attn": attn_state()}

    if per_lane:
        pos = jnp.zeros((Z, bsz), jnp.int32)
    else:
        pos = jnp.array(0, jnp.int32)
    cache: Dict[str, Any] = {"layers": layers, "pos": pos}
    if ring and cfg.family not in ("ssm",):
        kp = jnp.full((Sc,), RING_INIT_POS, jnp.int32)
        cache["k_pos"] = (jnp.broadcast_to(kp, (Z, bsz, Sc)) if per_lane
                         else kp)
    return cache


def _where_lanes(mask: jnp.ndarray, new_tree, old_tree, lead: int = 1):
    """Per-lane tree select: take ``new`` where ``mask`` ([Z, b] bool),
    keep ``old`` elsewhere. ``lead`` = leading dims before the (Z, b)
    axes (1 for [L, Z, b, ...] layer-state leaves, 0 for [Z, b, ...]).
    Untouched lanes stay bitwise identical (jnp.where is a select)."""

    def sel(n, o):
        m = mask.reshape((1,) * lead + mask.shape
                         + (1,) * (n.ndim - lead - mask.ndim))
        return jnp.where(m, n, o)

    return jax.tree_util.tree_map(sel, new_tree, old_tree)


def decode_step(cfg: ModelConfig, params: Dict, lora: Dict, cache: Dict,
                tokens: jnp.ndarray,
                active: Optional[jnp.ndarray] = None
                ) -> Tuple[jnp.ndarray, Dict]:
    """One decode step. tokens: [Z, b] int32 -> (logits [Z,b,V], cache').

    With a GLOBAL position cache (``cache["pos"]`` scalar) every lane
    writes/reads at the same position — the historical round-batching
    path. With a PER-LANE cache (``pos`` is [Z, b]) each (slot, lane)
    stream carries its own position: K/V writes scatter at each lane's
    own index, RoPE angles and the causal bias are built per lane, and a
    lane never sees keys beyond its own position — so neighbors mid-join
    or mid-retirement cannot perturb it. ``active`` ([Z, b] bool,
    per-lane caches only) freezes idle lanes: their cache, position and
    recurrent state stay bitwise untouched while live lanes advance."""
    Z, bsz = tokens.shape
    pos = cache["pos"]
    per_lane = getattr(pos, "ndim", 0) == 2
    assert active is None or per_lane, "active mask needs a per-lane cache"
    x = _embed(cfg, params, tokens[:, :, None], None)
    if per_lane:
        positions = pos[..., None]                     # [Z, b, 1]
        if cfg.rope.is_mrope:
            positions = jnp.broadcast_to(positions, (3, Z, bsz, 1))
    else:
        positions = text_positions((), 1, cfg.rope, offset=pos)

    ring = "k_pos" in cache
    ctx: Dict[str, Any] = {
        "angles": _angles(cfg, positions),
        "q_pos": pos[..., None] if per_lane else pos[None],
    }
    new_kpos = None
    if cfg.family != "ssm":
        if ring:
            W = cfg.sliding_window
            widx = jnp.mod(pos, W)
            if per_lane:
                sel = jnp.arange(W, dtype=jnp.int32)[None, None, :] \
                    == widx[..., None]                 # [Z, b, W]
                new_kpos = jnp.where(sel, pos[..., None], cache["k_pos"])
                if active is not None:
                    new_kpos = jnp.where(active[..., None], new_kpos,
                                         cache["k_pos"])
            else:
                new_kpos = jax.lax.dynamic_update_index_in_dim(
                    cache["k_pos"], pos, widx, axis=0)
            ctx.update(write_index=widx, k_pos=new_kpos, window=W)
        else:
            ctx.update(write_index=pos,
                       kv_valid_len=pos + 1,
                       window=_train_window(cfg))

    x, aux, new_states = _scan_layers(
        cfg, x, params, lora, ctx, cache["layers"],
        remat=False, need_state=True)
    x = rms_norm(x, params["final_norm"], cfg.norm_eps)
    logits = _unembed(cfg, params, x[:, :, 0])
    new_pos = pos + 1
    if active is not None:
        new_states = _where_lanes(active, new_states, cache["layers"])
        new_pos = jnp.where(active, new_pos, pos)
    new_cache = {"layers": new_states, "pos": new_pos}
    if new_kpos is not None:
        new_cache["k_pos"] = new_kpos
    return logits, new_cache


# ---------------------------------------------------------------------------
# Lane lifecycle (continuous batching over a per-lane cache)
# ---------------------------------------------------------------------------

def reset_lanes(cfg: ModelConfig, cache: Dict,
                lane_mask: jnp.ndarray) -> Dict:
    """Return a cache with the masked lanes reset to the just-initialized
    state (pos 0, zero K/V and recurrent state, ring slots pushed to the
    far past) — a fresh request can join those lanes of a LIVE cache.
    Unmasked lanes are bitwise untouched."""
    assert cache["pos"].ndim == 2, "reset_lanes needs a per-lane cache"
    layers = _where_lanes(
        lane_mask,
        jax.tree_util.tree_map(jnp.zeros_like, cache["layers"]),
        cache["layers"])
    out: Dict[str, Any] = {
        "layers": layers,
        "pos": jnp.where(lane_mask, 0, cache["pos"]).astype(jnp.int32),
    }
    if "k_pos" in cache:
        out["k_pos"] = jnp.where(lane_mask[..., None],
                                 jnp.int32(RING_INIT_POS), cache["k_pos"])
    return out


def prefill_lanes(cfg: ModelConfig, params: Dict, lora: Dict, cache: Dict,
                  tokens: jnp.ndarray, lane_mask: jnp.ndarray,
                  plens: Optional[jnp.ndarray] = None
                  ) -> Tuple[jnp.ndarray, Dict]:
    """Block-prefill a subset of lanes of a LIVE per-lane cache.

    tokens: [Z, b, P] int32 (rows of non-joining lanes are ignored);
    lane_mask: [Z, b] bool. The joining lanes' prompts are written into
    their own lane caches at offsets 0..P-1 and their positions set to P
    while every other lane — mid-decode or idle — stays bitwise
    untouched. Returns (last-token logits [Z, b, V], merged cache).

    ``plens`` ([Z, b] int32) serves RAGGED joins in one launch: each
    joining lane's true prompt length, with ``tokens`` right-padded to
    the common P. A lane's position is set to its own length and its
    logits taken at ``plens - 1``. The padded tail beyond a lane's
    length writes garbage K/V at indices >= len — harmless: causality
    masks index i until the lane's position reaches i, and decode
    rewrites index i (write-before-read) on the very step it first
    becomes visible, so padded prefill stays bitwise identical to an
    exact-length one.

    Non-ring attention families only (ring and recurrent families join
    by streaming the prompt through ``decode_step``)."""
    assert cache["pos"].ndim == 2, "prefill_lanes needs a per-lane cache"
    assert "k_pos" not in cache and cfg.family not in ("ssm", "hybrid"), \
        "block lane prefill supports non-ring attention caches only"
    Z, b, P = tokens.shape
    work = reset_lanes(cfg, cache, lane_mask)
    # forward writes ALL lanes at 0..P-1; only joining lanes are merged
    x = _embed(cfg, params, tokens, None)
    positions = text_positions((), P, cfg.rope)
    ctx: Dict[str, Any] = {
        "angles": _angles(cfg, positions),
        "q_pos": jnp.arange(P, dtype=jnp.int32),
        "window": _train_window(cfg),
        "write_index": jnp.array(0, jnp.int32),
    }
    x, _, new_states = _scan_layers(
        cfg, x, params, lora, ctx, work["layers"],
        remat=False, need_state=True)
    x = rms_norm(x, params["final_norm"], cfg.norm_eps)
    if plens is None:
        last = x[:, :, -1]
        new_pos = jnp.full_like(cache["pos"], P)
    else:
        idx = (plens.astype(jnp.int32) - 1)[:, :, None, None]
        last = jnp.take_along_axis(
            x, jnp.broadcast_to(idx, (Z, b, 1, x.shape[-1])), axis=2
        )[:, :, 0]
        new_pos = plens.astype(jnp.int32)
    logits = _unembed(cfg, params, last)
    merged = {
        "layers": _where_lanes(lane_mask, new_states, cache["layers"]),
        "pos": jnp.where(lane_mask, new_pos, cache["pos"]).astype(jnp.int32),
    }
    return logits, merged
