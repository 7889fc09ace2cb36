"""Plain reference of the dense decoder the benchmark's configurations
describe, in float32 with every matrix product at ``HIGHEST`` precision.

Written from the configuration file's semantics alone; it imports nothing
of the program. Per layer: RMSNorm (with weight), q/k/v projections each
plus a LoRA delta ``2 * (x A) B`` (alpha = 2r) at the adapter's true rank
(A's columns and B's rows beyond it are zero), rotary embedding on the
first ``partial_rotary_factor`` of each head (rotate-half pairs, inverse
frequencies ``theta ** (-i / half)``), causal grouped-query attention
(query head h reads key/value head h // G) with softmax in float32, the
output projection, a residual add, RMSNorm, SwiGLU MLP (silu(gate) * up,
then down, each with its LoRA delta), a residual add; then a final RMSNorm
and the output head. Training: mean next-token cross-entropy over the
labelled positions (label -1 is ignored), AdamW with per-adapter
global-norm gradient clipping.

``quant="fp8"`` is the control, the precision below the configurations'
bfloat16: every projection, LoRA product and the output head take float8
e4m3 operands (one scale per row of the activations and per output
column of the weights, the largest magnitude at 448); gradients pass the
rounding straight through, the rest as above.

Layers run in a scan with each layer's weights cast to float32 inside it,
and the training loss is rematerialised per layer, so the reference fits
on one chip at the published widths.
"""
from __future__ import annotations

from typing import Dict, List, Optional, Tuple

import jax
import jax.numpy as jnp

HIGHEST = jax.lax.Precision.HIGHEST


def _dims(spec: Dict):
    d = spec["hidden_size"]
    H, KV = spec["num_attention_heads"], spec["num_key_value_heads"]
    return d, H, KV, spec.get("head_dim") or d // H


def fake_fp8(x: jnp.ndarray, axis: int) -> jnp.ndarray:
    """x rounded to float8 e4m3 (one scale per slice along ``axis``, the
    largest magnitude at e4m3's 448); the gradient passes straight
    through."""
    s = jnp.max(jnp.abs(x), axis=axis, keepdims=True) / 448.0
    s = jnp.where(s > 0, s, 1.0)
    q = (x / s).astype(jnp.float8_e4m3fn).astype(x.dtype) * s
    return x + jax.lax.stop_gradient(q - x)


QUANT = {"fp8": fake_fp8}


def mm(x: jnp.ndarray, w: jnp.ndarray, quant: Optional[str]) -> jnp.ndarray:
    if quant is not None:
        x, w = QUANT[quant](x, -1), QUANT[quant](w, 0)
    return jnp.einsum("...i,io->...o", x, w, precision=HIGHEST)


def rms_norm(x, w, eps):
    return x * jax.lax.rsqrt(jnp.mean(x * x, -1, keepdims=True) + eps) * w


def rope(x: jnp.ndarray, spec: Dict) -> jnp.ndarray:
    """x: [n, S, heads, hd], positions 0..S-1."""
    hd = x.shape[-1]
    rot = int(hd * spec.get("partial_rotary_factor", 1.0))
    half = rot // 2
    inv = 1.0 / (spec["rope_theta"] ** (jnp.arange(half, dtype=jnp.float32)
                                        / half))
    ang = jnp.arange(x.shape[1], dtype=jnp.float32)[:, None] * inv
    c, s = jnp.cos(ang)[:, None, :], jnp.sin(ang)[:, None, :]
    x1, x2, rest = x[..., :half], x[..., half:rot], x[..., rot:]
    return jnp.concatenate([x1 * c - x2 * s, x1 * s + x2 * c, rest], -1)


def _layer(spec: Dict, quant: Optional[str], x, lp, ad):
    d, H, KV, hd = _dims(spec)
    eps = spec["rms_norm_eps"]
    scale = spec["lora"]["alpha_over_r"]
    n, S, _ = x.shape

    def lin(h, t):
        y = mm(h, lp[t], quant)
        if t in ad:
            y = y + scale * mm(mm(h, ad[t]["A"], quant), ad[t]["B"], quant)
        return y

    h = rms_norm(x, lp["attn_norm"], eps)
    q = rope(lin(h, "q_proj").reshape(n, S, H, hd), spec)
    k = rope(lin(h, "k_proj").reshape(n, S, KV, hd), spec)
    v = lin(h, "v_proj").reshape(n, S, KV, hd)
    k = jnp.repeat(k, H // KV, axis=2)
    v = jnp.repeat(v, H // KV, axis=2)
    s = jnp.einsum("nqhd,nkhd->nhqk", q, k, precision=HIGHEST) * hd ** -0.5
    causal = jnp.arange(S)[None, :] <= jnp.arange(S)[:, None]
    s = jnp.where(causal, s, -jnp.inf)
    p = jax.nn.softmax(s, axis=-1)
    o = jnp.einsum("nhqk,nkhd->nqhd", p, v, precision=HIGHEST)
    x = x + lin(o.reshape(n, S, H * hd), "o_proj")
    h = rms_norm(x, lp["mlp_norm"], eps)
    g = lin(h, "gate_proj")
    x = x + lin(jax.nn.silu(g) * lin(h, "up_proj"), "down_proj")
    return x


def hidden(spec: Dict, params: Dict, adapter: Dict, tokens: jnp.ndarray,
           quant: Optional[str] = None, remat: bool = False) -> jnp.ndarray:
    """Final hidden states [n, S, d] of ``tokens`` [n, S]; ``adapter`` is
    {target: {"A": [L, d_in, r], "B": [L, r, d_out]}}."""
    x = params["embed"][tokens].astype(jnp.float32)

    def body(x, xs):
        lp, ad = jax.tree_util.tree_map(
            lambda a: a.astype(jnp.float32), xs)
        return _layer(spec, quant, x, lp, ad), None

    if remat:
        body = jax.checkpoint(body)
    x, _ = jax.lax.scan(body, x, (params["layers"], adapter))
    return rms_norm(x, params["final_norm"].astype(jnp.float32),
                    spec["rms_norm_eps"])


def head(spec: Dict, params: Dict) -> jnp.ndarray:
    W = params["embed"].T if spec["tie_word_embeddings"] \
        else params["lm_head"]
    return W.astype(jnp.float32)


def logits(spec: Dict, params: Dict, adapter: Dict, tokens: jnp.ndarray,
           quant: Optional[str] = None) -> jnp.ndarray:
    return mm(hidden(spec, params, adapter, tokens, quant),
              head(spec, params), quant)


# ---------------------------------------------------------------------------
# training
# ---------------------------------------------------------------------------

def loss(spec: Dict, params: Dict, adapter: Dict, tokens: jnp.ndarray,
         labels: jnp.ndarray, quant: Optional[str] = None) -> jnp.ndarray:
    """Mean next-token cross-entropy over positions with label >= 0."""
    lg = mm(hidden(spec, params, adapter, tokens, quant, remat=True),
            head(spec, params), quant)
    lse = jax.nn.logsumexp(lg, axis=-1)
    gold = jnp.take_along_axis(lg, jnp.maximum(labels, 0)[..., None],
                               -1)[..., 0]
    mask = (labels >= 0).astype(jnp.float32)
    return jnp.sum((lse - gold) * mask) / jnp.maximum(jnp.sum(mask), 1.0)


def _mask_rank(adapter: Dict, rank) -> Dict:
    def one(name, x):
        keep = (jnp.arange(x.shape[-1 if name == "A" else -2]) < rank)
        keep = keep.astype(x.dtype)
        return x * (keep if name == "A" else keep[:, None])
    return {t: {m: one(m, ab[m]) for m in ab} for t, ab in adapter.items()}


def adamw_step(spec: Dict, params: Dict, state: Tuple, batch: Dict,
               hp: Dict, quant: Optional[str] = None):
    """One AdamW step of one adapter. ``state`` = (adapter, m, v, t);
    returns (state', loss, gradient as the optimizer took it: clipped to
    global norm ``grad_clip``)."""
    adapter, m, v, t = state
    lval, g = jax.value_and_grad(
        lambda a: loss(spec, params, a, batch["tokens"], batch["labels"],
                       quant))(adapter)
    leaves = jax.tree_util.tree_leaves(g)
    norm = jnp.sqrt(sum(jnp.sum(x * x) for x in leaves))
    clip = jnp.where(norm > hp["grad_clip"], hp["grad_clip"] / norm, 1.0)
    g = jax.tree_util.tree_map(lambda x: x * clip, g)
    t = t + 1
    b1, b2 = hp["beta1"], hp["beta2"]
    m = jax.tree_util.tree_map(lambda a, b: b1 * a + (1 - b1) * b, m, g)
    v = jax.tree_util.tree_map(lambda a, b: b2 * a + (1 - b2) * b * b, v, g)

    def upd(p, mm_, vv):
        mhat = mm_ / (1 - b1 ** t)
        vhat = vv / (1 - b2 ** t)
        return p - hp["lr"] * (mhat / (jnp.sqrt(vhat) + hp["eps"])
                               + hp["wd"] * p)

    adapter = _mask_rank(jax.tree_util.tree_map(upd, adapter, m, v),
                         hp["rank"])
    return (adapter, m, v, t), lval, g


def train(spec: Dict, params: Dict, adapter: Dict, batches: List[Dict],
          hp: Dict, quant: Optional[str] = None):
    """``len(batches)`` AdamW steps of one adapter from ``adapter``.
    Returns (losses, first gradient as the optimizer took it, adapter
    after the last step)."""
    step = jax.jit(lambda p, s, b, h: adamw_step(spec, p, s, b, h, quant))
    zeros = jax.tree_util.tree_map(jnp.zeros_like, adapter)
    state = (adapter, zeros, zeros, jnp.zeros((), jnp.float32))
    losses, first = [], None
    hp = {k: jnp.asarray(v, jnp.float32) for k, v in hp.items()}
    for b in batches:
        state, lval, g = step(params, state, b, hp)
        losses.append(float(lval))
        if first is None:
            first = jax.tree_util.tree_map(jax.device_get, g)
    return losses, first, jax.tree_util.tree_map(jax.device_get, state[0])


# ---------------------------------------------------------------------------
# serving
# ---------------------------------------------------------------------------

def served_gaps(spec: Dict, params: Dict, adapter: Dict, seq: jnp.ndarray,
                targets: jnp.ndarray, control: Optional[str] = None):
    """For one sequence ``seq`` [1, S] (prompt, served tokens, padding)
    and ``targets`` [S] (the token served after each position, -1 where
    none): at each position the gap by which the served token's logit lies
    below the reference's best. With ``control`` (a ``quant``), also the
    gap of the token the reference in that precision puts first there."""
    lg = logits(spec, params, adapter, seq)[0]
    best = jnp.max(lg, axis=-1)
    valid = targets >= 0
    pick = jnp.take_along_axis(lg, jnp.maximum(targets, 0)[:, None], -1)[:, 0]
    served = jnp.where(valid, best - pick, 0.0)
    if not control:
        return served, None
    q = jnp.argmax(logits(spec, params, adapter, seq, control)[0], axis=-1)
    alt = jnp.take_along_axis(lg, q[:, None], -1)[:, 0]
    return served, jnp.where(valid, best - alt, 0.0)
