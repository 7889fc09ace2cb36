"""Step builders: train / eval / prefill / serve.

These pure functions are what both the local engine (jax.jit) and the
multi-pod launcher (pjit with shardings, launch/train.py) compile. The base
model ``params`` is a frozen (non-differentiated) input; gradients flow only
through the slot-stacked LoRA tree.
"""
from __future__ import annotations

import contextlib
import functools
from typing import Callable, Dict

import jax
import jax.numpy as jnp

from repro.configs.base import ModelConfig
from repro.core import lora as LORA
from repro.core import losses as LS
from repro.core.lora import mask_lora_tree
from repro.models import model as M
from repro.optim import adamw


def make_train_step(cfg: ModelConfig, *, loss_kind: str = "sft",
                    remat: bool = True) -> Callable:
    """train_step(params, lora, opt_state, hp, active, ranks, batch)
    -> (lora', opt_state', metrics{per_slot_loss[Z], grad_norm[Z]}).

    ``batch`` may carry ``slot_rows`` ([Z] int32, valid token rows per
    slot in flattened b*seq units): ragged slot widths — LoRA deltas are
    then computed over only each slot's own rows (the ragged grouped-GEMM
    path; zero delta and zero gradient on padding rows). It may also carry
    ``slot_ranks`` ([Z] int32, per-slot TRUE adapter ranks from the
    executor's SlotManager): LoRA deltas then confine each slot to its
    first ranks[z] rank rows/columns (the rank-local grouped-GEMM path —
    the padded rank region is masked on load, gets exactly zero gradient,
    and the post-step rank re-mask is redundant)."""
    loss_fn_inner = {"sft": LS.sft_loss, "dpo": LS.dpo_loss}[loss_kind]

    def train_step(params, lora, opt_state, hp: adamw.SlotHParams,
                   active: jnp.ndarray, ranks: jnp.ndarray, batch: Dict):
        batch = dict(batch)
        slot_rows = batch.pop("slot_rows", None)
        slot_ranks = batch.pop("slot_ranks", None)

        def loss_fn(lora_):
            total, per_slot = loss_fn_inner(cfg, params, lora_, batch,
                                            active, remat=remat)
            return total, per_slot

        with contextlib.ExitStack() as ctx:
            if slot_rows is not None:
                ctx.enter_context(LORA.ragged_rows(slot_rows))
            if slot_ranks is not None:
                ctx.enter_context(LORA.slot_ranks(slot_ranks))
            (_, per_slot), grads = jax.value_and_grad(
                loss_fn, has_aux=True)(lora)
        norms = adamw.per_slot_global_norm(grads)
        masker = functools.partial(mask_lora_tree, ranks=ranks,
                                   r_max=cfg.lora.r_max)
        new_lora, new_opt = adamw.apply_updates(
            lora, grads, opt_state, hp, active,
            rank_masker=lambda t: masker(t))
        metrics = {"per_slot_loss": per_slot, "grad_norm": norms}
        return new_lora, new_opt, metrics

    return train_step


def jit_train_step(cfg: ModelConfig, *, loss_kind: str = "sft") -> Callable:
    """``make_train_step`` jitted with the slot LoRA tree and optimizer
    state (args 1 and 2) donated: the update writes into their buffers,
    so a step holds one copy of the adapter state, not the old and the new
    side by side. Callers must drop their references to the inputs and
    keep the returned trees."""
    return jax.jit(make_train_step(cfg, loss_kind=loss_kind),
                   donate_argnums=(1, 2))


def make_eval_step(cfg: ModelConfig, *, loss_kind: str = "sft") -> Callable:
    """eval_step(params, lora, active, batch) -> per-slot val loss [Z].

    ``batch`` may carry ``slot_ranks`` like the train step (eval rides the
    same rank-local LoRA path as training on mixed-rank replicas)."""
    loss_fn_inner = {"sft": LS.sft_loss, "dpo": LS.dpo_loss}[loss_kind]

    def eval_step(params, lora, active, batch):
        batch = dict(batch)
        slot_ranks = batch.pop("slot_ranks", None)
        ctx = (LORA.slot_ranks(slot_ranks) if slot_ranks is not None
               else contextlib.nullcontext())
        with ctx:
            _, per_slot = loss_fn_inner(cfg, params, lora, batch, active,
                                        remat=False)
        return per_slot

    return eval_step


def make_prefill_step(cfg: ModelConfig) -> Callable:
    """prefill_step(params, lora, batch) -> (last-token logits, cache)."""

    def prefill_step(params, lora, cache, batch):
        h, _, new_cache = M.forward(
            cfg, params, lora, batch["tokens"],
            positions=batch.get("positions"),
            modal_embeds=batch.get("modal_embeds"),
            cache=cache, remat=False)
        logits = M._unembed(cfg, params, h[:, :, -1])
        return logits, new_cache

    return prefill_step


def make_serve_step(cfg: ModelConfig) -> Callable:
    """serve_step(params, lora, cache, tokens[Z,b], active=None)
    -> (logits, cache').

    ``active`` ([Z, b] bool) is the per-lane continuous-batching mask:
    inactive lanes neither write their cache nor advance their position
    (idle lanes stay bitwise frozen while live lanes decode). Requires a
    per-lane cache (``init_cache(..., per_lane=True)``)."""

    def serve_step(params, lora, cache, tokens, active=None):
        return M.decode_step(cfg, params, lora, cache, tokens,
                             active=active)

    return serve_step


def make_lane_prefill_step(cfg: ModelConfig) -> Callable:
    """lane_prefill(params, lora, cache, tokens[Z,b,P], lane_mask[Z,b],
    plens[Z,b]) -> (last-token logits, cache') — block prefill of a
    subset of lanes of a live per-lane cache (ragged prompt lengths via
    ``plens``, tokens right-padded to P); every other lane bitwise
    untouched."""

    def lane_prefill(params, lora, cache, tokens, lane_mask, plens):
        return M.prefill_lanes(cfg, params, lora, cache, tokens,
                               lane_mask, plens)

    return lane_prefill


def make_join_decode_step(cfg: ModelConfig) -> Callable:
    """join_decode(params, lora, cache, tokens[Z,b,P], lane_mask[Z,b],
    plens[Z,b], cur[Z,b], active[Z,b]) -> (prefill_greedy, logits,
    decode_greedy, cache') — block-prefill the masked lanes AND run one
    fused decode step over (active | joined) lanes in a SINGLE launch.

    Each joiner's first token is its greedy prefill argmax, chosen
    on-device and fed straight into the decode — no host round-trip
    between the prefill and the step that consumes its first token.
    Greedy joiners only (a sampled first token needs the host)."""

    def join_decode(params, lora, cache, tokens, lane_mask, plens, cur,
                    active):
        p_logits, cache = M.prefill_lanes(cfg, params, lora, cache,
                                          tokens, lane_mask, plens)
        p_greedy = jnp.argmax(p_logits, axis=-1)
        cur = jnp.where(lane_mask, p_greedy.astype(cur.dtype), cur)
        live = jnp.logical_or(active, lane_mask)
        logits, cache = M.decode_step(cfg, params, lora, cache, cur,
                                      active=live)
        return p_greedy, logits, jnp.argmax(logits, axis=-1), cache

    return join_decode
