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

Each slot write and snapshot is a ``TraceAnnotation`` span (``tune.admit``,
``tune.restore``, ``tune.evict``, ``tune.snapshot``) on the profiler's
clock; a snapshot's span counts the bytes it copies to the host.
"""
from __future__ import annotations

import dataclasses
from typing import Dict, List, Optional

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


def _i_slot(tree: Dict, slot: int, sub: Dict) -> Dict:
    return jax.tree_util.tree_map(
        lambda full, one: full.at[:, slot].set(jnp.asarray(one)), tree, sub)


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

    # ---- admission ---------------------------------------------------------
    def admit(self, slot: int, job_id: str, tc: TrainConfig,
              key: jax.Array, task: Optional[str] = None,
              b: int = 0, seq: int = 0) -> None:
        """Fresh job into a slot: new init, zeroed moments, job's hparams,
        and the job's own (b, seq) width."""
        assert self.slot_jobs[slot] is None, f"slot {slot} occupied"
        rank = min(tc.lora_rank, self.cfg.lora.r_max)
        with TraceAnnotation("tune.admit"):
            one = LORA.init_lora_tree(
                key, self.cfg, 1, jnp.array([rank]), self.target_shapes)
            sub = jax.tree_util.tree_map(lambda x: x[:, 0], one)
            self.lora = _i_slot(self.lora, slot, sub)
            self.opt_state = adamw.reset_slot(self.opt_state, slot)
            self.ranks = self.ranks.at[slot].set(rank)
            self.active = self.active.at[slot].set(1)
        self.hp = self.hp.replace_slot(
            slot, lr=tc.learning_rate, wd=tc.weight_decay,
            beta1=tc.beta1, beta2=tc.beta2, grad_clip=tc.grad_clip)
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
            self.lora = _i_slot(self.lora, slot, snap.lora)
            mu = _i_slot(self.opt_state.mu, slot, snap.mu)
            nu = _i_slot(self.opt_state.nu, slot, snap.nu)
            cnt = self.opt_state.count.at[slot].set(snap.count)
            self.opt_state = adamw.AdamWState(mu, nu, cnt)
            self.ranks = self.ranks.at[slot].set(snap.rank)
            self.active = self.active.at[slot].set(1)
        self.hp = self.hp.replace_slot(
            slot, lr=tc.learning_rate, wd=tc.weight_decay,
            beta1=tc.beta1, beta2=tc.beta2, grad_clip=tc.grad_clip)
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
            self.lora = LORA.zero_slot(self.lora, slot)
            self.opt_state = adamw.reset_slot(self.opt_state, slot)
            self.active = self.active.at[slot].set(0)
            self.ranks = self.ranks.at[slot].set(0)
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
