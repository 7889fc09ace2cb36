"""Slot-stacked adapter runtime state + host-side slot management.

Fixed ``Z`` device slots hold adapters with static shapes (r_max-padded), so
the early-exit controller can admit/evict/rotate jobs with pure functional
array updates — never a recompile. Rotated-out jobs are snapshotted to host
(params + optimizer moments + step count) and restored bit-exactly when
they continue training (paper §5.2: survivors "carry over their optimizer
states and loss histories").

Layer contract — SlotSnapshot bit-exactness: ``snapshot()`` followed by
``restore()`` reproduces the job's device state exactly (adapter params,
AdamW moments, step count, slot width/rank), on ANY slot of ANY same-shape
replica. Together with task-local lifecycle state (lane-indexed batch
streams, monitors, init keys) this is the primitive that makes slot-level
preemption and cross-replica migration invisible to the loss trajectory:
a migrated job's subsequent losses are bitwise identical to never moving
(tests/test_lora_isolation.py).

Each slot write is one jitted program: a slot index and a rank are
traced scalars, so one compilation serves every slot and rank of a sweep.
The optimizer state, two thirds of the slot state's bytes, is donated
and written in place; the adapter tree is not donated, so an adapter tree
a caller took before the write stays readable (bench/faults.py's refill
faults put it back), at the cost of one on-device copy of it a write.
Admission and eviction share a program (eviction is an admission with
``live`` 0 and rank 0); a restore has its own, fed from the snapshot's
host arrays.

Each slot write and snapshot is a ``TraceAnnotation`` span (``tune.admit``,
``tune.restore``, ``tune.evict``, ``tune.snapshot``) on the profiler's
clock; a snapshot's span counts the bytes it copies to the host.
"""
from __future__ import annotations

import dataclasses
import functools
from typing import Dict, List, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np
from jax.profiler import TraceAnnotation

from repro.configs.base import ModelConfig, TrainConfig
from repro.core import lora as LORA
from repro.optim import adamw


@dataclasses.dataclass
class SlotSnapshot:
    """Host copy of one job's device state (for warmup rotation).

    ``per_adapter_batch``/``seq_len`` record the job's slot WIDTH — slots
    are ragged (variable-width) since co-located tasks may train with
    different batch sizes — so a restore re-establishes the exact same
    token footprint the job had before rotation."""
    job_id: str
    lora: Dict                    # [L, ...] single-adapter tree
    mu: Dict
    nu: Dict
    count: int
    rank: int
    per_adapter_batch: int = 0
    seq_len: int = 0


def _x_slot(tree: Dict, slot: int) -> Dict:
    return jax.tree_util.tree_map(lambda x: np.asarray(x[:, slot]), tree)


def _slot_hp(tc: TrainConfig) -> adamw.SlotHParams:
    """A job's hyperparameters as one slot's row of ``SlotHParams``."""
    return adamw.SlotHParams(
        lr=np.float32(tc.learning_rate), wd=np.float32(tc.weight_decay),
        beta1=np.float32(tc.beta1), beta2=np.float32(tc.beta2),
        grad_clip=np.float32(tc.grad_clip))


# eviction keeps the slot's hyperparameters: its row argument goes unread
_KEEP_HP = adamw.SlotHParams(
    **dict.fromkeys(adamw.SlotHParams._fields, np.float32(0.0)))


def _put_hp(hps: adamw.SlotHParams, slot, row: adamw.SlotHParams,
            live=1) -> adamw.SlotHParams:
    return jax.tree_util.tree_map(
        lambda v, h: v.at[slot].set(jnp.where(live, h, v[slot])), hps, row)


@functools.partial(jax.jit, static_argnums=(0, 1, 2), donate_argnums=(4,))
def _admit_program(cfg: ModelConfig, target_shapes: Tuple, reset_slot,
                   lora: Dict, opt: adamw.AdamWState, small: Tuple,
                   key: jax.Array, slot: jnp.ndarray, rank: jnp.ndarray,
                   live: jnp.ndarray, hp: adamw.SlotHParams):
    """Admit (``live`` 1) or evict (``live`` 0, ``rank`` 0) one slot.

    Admission writes ``init_lora_tree(key, cfg, 1, [rank], ...)``'s
    adapter, bit for bit, and the job's hyperparameters ``hp``; eviction
    writes +0.0 (a ``where``, not a product with a zero mask, which would
    leave -0.0) and keeps the slot's hyperparameters. Both zero the slot's
    moments and step count through ``reset_slot`` and set its rank and
    active flag. ``reset_slot`` is the caller's ``adamw.reset_slot`` as it
    stands at the call: a static argument, so a replaced reset is traced
    anew and not served from the cache. ``small`` is (ranks, active, hp
    vectors)."""
    ranks, active, hps = small
    fresh = LORA.init_lora_tree(key, cfg, 1, rank[None], dict(target_shapes))
    lora = jax.tree_util.tree_map(
        lambda full, one: full.at[:, slot].set(
            jnp.where(live, one[:, 0], 0.0)), lora, fresh)
    return lora, reset_slot(opt, slot), (
        ranks.at[slot].set(rank), active.at[slot].set(live),
        _put_hp(hps, slot, hp, live))


@functools.partial(jax.jit, donate_argnums=(1,))
def _restore_program(lora: Dict, opt: adamw.AdamWState, small: Tuple,
                     slot: jnp.ndarray, snap_lora: Dict, mu: Dict, nu: Dict,
                     count: jnp.ndarray, rank: jnp.ndarray,
                     hp: adamw.SlotHParams):
    """Write a snapshot's adapter, moments, step count and rank, and the
    job's hyperparameters ``hp``, into one slot, and activate it."""
    ranks, active, hps = small

    def put(tree, sub):
        return jax.tree_util.tree_map(
            lambda full, one: full.at[:, slot].set(one), tree, sub)

    opt = adamw.AdamWState(put(opt.mu, mu), put(opt.nu, nu),
                           opt.count.at[slot].set(count))
    return put(lora, snap_lora), opt, (ranks.at[slot].set(rank),
                                       active.at[slot].set(1),
                                       _put_hp(hps, slot, hp))


class SlotManager:
    """Owns the device arrays for one executor's Z adapter slots.

    Slots are tagged with the *task* that owns them (``slot_tasks``) so one
    frozen-backbone replica can host adapter slots belonging to different
    tasks concurrently (cross-task co-location): the shared executor
    attributes per-slot losses, checkpoints, and evictions to the owning
    task's lifecycle through these tags.

    Slot WIDTH is a per-slot property (``slot_b``/``slot_seq``): co-located
    tasks may train with different per-adapter batch sizes and seq lens
    (ragged slots). The executor packs each slot's own (b, seq) rows into
    its lane and routes per-slot token-row counts to the ragged grouped-
    GEMM path; ``slot_tokens`` is what admission budgets against."""

    def __init__(self, cfg: ModelConfig, Z: int,
                 target_shapes: Dict, key: jax.Array):
        self.cfg = cfg
        self.Z = Z
        self.target_shapes = target_shapes
        self.ranks = jnp.zeros((Z,), jnp.int32)
        self.active = jnp.zeros((Z,), jnp.int32)
        self.hp = adamw.SlotHParams.broadcast(Z)
        self.lora = LORA.init_lora_tree(
            key, cfg, Z, jnp.zeros((Z,), jnp.int32), target_shapes)
        self.opt_state = adamw.init_state(self.lora, Z)
        self.slot_jobs: List[Optional[str]] = [None] * Z
        self.slot_tasks: List[Optional[str]] = [None] * Z
        self.slot_b: List[int] = [0] * Z        # per-slot batch width
        self.slot_seq: List[int] = [0] * Z      # per-slot seq len
        # host mirror of ``ranks``: the per-step rank-local dispatch and
        # the §A.3 rank accounting must not sync a device array
        self.slot_rank: List[int] = [0] * Z
        self._shapes = tuple(target_shapes.items())   # a static argument
        self._evict_key = key

    def _small(self) -> Tuple:
        return self.ranks, self.active, self.hp

    def _set_state(self, out) -> None:
        self.lora, self.opt_state, (self.ranks, self.active, self.hp) = out

    # ---- admission ---------------------------------------------------------
    def admit(self, slot: int, job_id: str, tc: TrainConfig,
              key: jax.Array, task: Optional[str] = None,
              b: int = 0, seq: int = 0) -> None:
        """Fresh job into a slot: new init, zeroed moments, job's hparams,
        and the job's own (b, seq) width."""
        assert self.slot_jobs[slot] is None, f"slot {slot} occupied"
        rank = min(tc.lora_rank, self.cfg.lora.r_max)
        with TraceAnnotation("tune.admit"):
            self._set_state(_admit_program(
                self.cfg, self._shapes, adamw.reset_slot, self.lora,
                self.opt_state, self._small(), key,
                np.int32(slot), np.int32(rank), np.int32(1), _slot_hp(tc)))
        self.slot_jobs[slot] = job_id
        self.slot_tasks[slot] = task
        self.slot_b[slot] = b or tc.per_adapter_batch
        self.slot_seq[slot] = seq
        self.slot_rank[slot] = rank

    def restore(self, slot: int, snap: SlotSnapshot, tc: TrainConfig,
                task: Optional[str] = None) -> None:
        """Rotate a snapshotted job back in (bit-exact continuation,
        including its slot width)."""
        assert self.slot_jobs[slot] is None, f"slot {slot} occupied"
        with TraceAnnotation("tune.restore"):
            self._set_state(_restore_program(
                self.lora, self.opt_state, self._small(), np.int32(slot),
                snap.lora, snap.mu, snap.nu, np.int32(snap.count),
                np.int32(snap.rank), _slot_hp(tc)))
        self.slot_jobs[slot] = snap.job_id
        self.slot_tasks[slot] = task
        self.slot_b[slot] = snap.per_adapter_batch or tc.per_adapter_batch
        self.slot_seq[slot] = snap.seq_len
        self.slot_rank[slot] = snap.rank

    # ---- eviction ----------------------------------------------------------
    def snapshot(self, slot: int) -> SlotSnapshot:
        job_id = self.slot_jobs[slot]
        assert job_id is not None
        with TraceAnnotation("tune.snapshot") as sp:
            snap = SlotSnapshot(
                job_id=job_id,
                lora=_x_slot(self.lora, slot),
                mu=_x_slot(self.opt_state.mu, slot),
                nu=_x_slot(self.opt_state.nu, slot),
                count=int(self.opt_state.count[slot]),
                rank=int(self.ranks[slot]),
                per_adapter_batch=self.slot_b[slot],
                seq_len=self.slot_seq[slot],
            )
            sp.set_metadata(d2h_bytes=sum(
                x.nbytes for x in jax.tree_util.tree_leaves(
                    (snap.lora, snap.mu, snap.nu))))
        return snap

    def evict(self, slot: int) -> None:
        """Drop a job: zero params + moments, deactivate (paper §5.2:
        'evicted adapters' parameters and optimizer states are discarded')."""
        with TraceAnnotation("tune.evict"):
            self._set_state(_admit_program(
                self.cfg, self._shapes, adamw.reset_slot, self.lora,
                self.opt_state, self._small(), self._evict_key,
                np.int32(slot), np.int32(0), np.int32(0), _KEEP_HP))
        self.slot_jobs[slot] = None
        self.slot_tasks[slot] = None
        self.slot_b[slot] = 0
        self.slot_seq[slot] = 0
        self.slot_rank[slot] = 0

    # ---- queries -----------------------------------------------------------
    def free_slots(self) -> List[int]:
        return [i for i, j in enumerate(self.slot_jobs) if j is None]

    def slot_tokens(self, slot: int) -> int:
        """Token footprint of one slot per fused step (b * seq)."""
        return self.slot_b[slot] * max(self.slot_seq[slot], 1)

    def occupied_tokens(self) -> int:
        """Total tokens per fused step across occupied slots — the ragged
        quantity the §A.3 memory model budgets (M_hat is token-linear)."""
        return sum(self.slot_tokens(i) for i, j in
                   enumerate(self.slot_jobs) if j is not None)

    def mixed_rank(self, r_max: int) -> bool:
        """True iff some occupied slot's true rank is below r_max — the
        executor's per-step dispatch predicate for the rank-local LoRA
        path (a homogeneous full-rank mix has no rank to mask and stays
        on the bitwise-identical dense/ragged path)."""
        return any(j is not None and self.slot_rank[i] < r_max
                   for i, j in enumerate(self.slot_jobs))

    def occupied_rank_tokens(self) -> int:
        """Total rank-weighted FLOP-tokens per fused step (sum of
        b_z * seq_z * rank_z over occupied slots) — what the rank-aware
        §A.3 budget charges instead of tokens * r_max."""
        return sum(self.slot_tokens(i) * self.slot_rank[i]
                   for i, j in enumerate(self.slot_jobs) if j is not None)

    def occupied(self) -> Dict[str, int]:
        return {j: i for i, j in enumerate(self.slot_jobs) if j is not None}

    def occupied_of(self, task: Optional[str]) -> Dict[str, int]:
        """{job_id: slot} for the slots tagged with ``task``."""
        return {j: i for i, j in enumerate(self.slot_jobs)
                if j is not None and self.slot_tasks[i] == task}

    def adapter_of(self, job_id: str) -> Dict:
        slot = self.occupied()[job_id]
        return _x_slot(self.lora, slot)

    def adapter_at(self, slot: int) -> Dict:
        """Host copy of one slot's adapter params (task-tag agnostic — the
        shared executor addresses slots by index, never by job id, so
        co-located tasks may reuse job names without colliding)."""
        assert self.slot_jobs[slot] is not None, f"slot {slot} empty"
        return _x_slot(self.lora, slot)

    def adapters_of(self, task: Optional[str]) -> Dict[str, Dict]:
        """{job_id: [L, ...] adapter sub-tree} for one task's (possibly
        non-contiguous) slots on a shared executor."""
        occ = self.occupied_of(task)
        if not occ:
            return {}
        jobs = sorted(occ)
        stacked = LORA.gather_slots(self.lora, [occ[j] for j in jobs])
        return {j: jax.tree_util.tree_map(
                    lambda x, i=i: np.asarray(x[:, i]), stacked)
                for i, j in enumerate(jobs)}
