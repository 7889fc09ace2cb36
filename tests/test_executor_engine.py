"""Integration: BatchedExecutor + Engine end-to-end on a tiny model."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import engine as alto
from repro.core.adapter_state import SlotManager
from repro.core.early_exit import EarlyExitConfig
from repro.core.executor import (BatchedExecutor, SharedBackboneExecutor,
                                 TaskLifecycle)
from repro.configs.base import TrainConfig
from repro.data.synthetic import SlotBatcher, make_task_dataset
from repro.models import model as M
from tests.conftest import reduced_f32


@pytest.fixture(scope="module")
def env():
    cfg = reduced_f32("paper-llama-tiny", num_layers=2, d_model=128,
                      vocab=256)
    ds = make_task_dataset("t", cfg.vocab_size, seq_len=32, num_train=64,
                           num_val=16, difficulty=0.2)
    params = M.init_params(jax.random.PRNGKey(0), cfg)
    return cfg, ds, params


def test_slot_snapshot_restore_bit_exact(env):
    cfg, ds, params = env
    mgr = SlotManager(cfg, 2, M.target_shapes(cfg), jax.random.PRNGKey(1))
    tc = TrainConfig(learning_rate=3e-3, lora_rank=4)
    mgr.admit(0, "job-a", tc, jax.random.PRNGKey(2))
    before = jax.tree_util.tree_map(np.asarray, mgr.lora)
    snap = mgr.snapshot(0)
    mgr.evict(0)
    assert mgr.slot_jobs[0] is None
    assert float(jnp.abs(mgr.lora["q_proj"]["A"][:, 0]).max()) == 0.0
    mgr.restore(0, snap, tc)
    after = jax.tree_util.tree_map(np.asarray, mgr.lora)
    for t in before:
        np.testing.assert_array_equal(before[t]["A"], after[t]["A"])
        np.testing.assert_array_equal(before[t]["B"], after[t]["B"])


def test_eval_runs_in_chunks_of_the_task_width(env):
    """eval_task runs the validation rows in chunks of the task's widest
    per-adapter batch (an eval holds no more than a train step); the
    token-weighted mean of the chunks is the one-batch eval."""
    cfg, ds, params = env
    ex = SharedBackboneExecutor(cfg, params, Z=2, per_adapter_batch=4)
    lc = TaskLifecycle(ex, "t", {"j": TrainConfig(lora_rank=4,
                                                  per_adapter_batch=4)},
                       total_steps=4, dataset=ds)
    ex.slots.lora = {t: {"A": ab["A"], "B": 0.1 * jax.random.normal(
                         jax.random.PRNGKey(3), ab["B"].shape)}
                     for t, ab in ex.slots.lora.items()}
    step, shapes = ex._eval_step, []

    def spy(*args):
        shapes.append(args[-1]["tokens"].shape)
        return step(*args)

    ex._eval_step = spy
    got = ex.eval_task(lc)
    rows = {k: jnp.asarray(v) for k, v in lc.batcher.val_batch_dict().items()}
    whole = np.asarray(step(params, ex.slots.lora, ex.slots.active, rows))
    assert shapes == [(2, 4, 32)] * 4           # 16 rows, 4 per chunk
    np.testing.assert_allclose(got, whole, rtol=1e-5)


def test_executor_full_lifecycle(env):
    cfg, ds, params = env
    ex = BatchedExecutor(cfg, params, ds, Z=2, per_adapter_batch=4,
                         ee=EarlyExitConfig(warmup_ratio=0.2,
                                            select_ratio=0.5),
                         eval_every=2, seed=0)
    jobs = {
        "good": TrainConfig(learning_rate=3e-3, lora_rank=8, max_steps=20),
        "lowlr": TrainConfig(learning_rate=1e-6, lora_rank=4, max_steps=20),
        "crazy": TrainConfig(learning_rate=500.0, lora_rank=8, max_steps=20),
        "ok": TrainConfig(learning_rate=1e-3, lora_rank=4, max_steps=20),
    }
    res = ex.run_task("task", jobs, total_steps=20)
    assert res.best_job in jobs
    assert np.isfinite(res.best_val)
    assert res.job_results[res.best_job].adapter is not None
    # every job got a terminal status
    for r in res.job_results.values():
        assert r.exit_reason is not None
    # warmup rotation trained every candidate at least warmup steps
    for r in res.job_results.values():
        assert r.steps_trained >= 4
    # early exit saved samples vs full grid
    assert 0.0 <= res.samples_saved_frac < 1.0


def test_diverging_lr_is_culled_by_patterns(env):
    """A genuinely diverging job must exit with fewer steps than budget."""
    cfg, ds, params = env
    ex = BatchedExecutor(cfg, params, ds, Z=2, per_adapter_batch=4,
                         ee=EarlyExitConfig(warmup_ratio=0.1,
                                            select_ratio=1.0),
                         eval_every=2, seed=0)
    jobs = {
        "good": TrainConfig(learning_rate=3e-3, lora_rank=8, max_steps=30),
        "diverge": TrainConfig(learning_rate=1000.0, lora_rank=8,
                               max_steps=30, grad_clip=0.0),
    }
    res = ex.run_task("task", jobs, total_steps=30)
    dj = res.job_results["diverge"]
    assert dj.exit_reason is not None
    # ALTO's contract: whoever wins, the winner ships the checkpoint of its
    # BEST validation point (a diverging config may legitimately win with
    # its pre-divergence best — paper §5.1 best-val checkpointing)
    assert np.isfinite(res.best_val)
    assert res.job_results[res.best_job].adapter is not None
    assert res.best_val <= res.job_results["good"].best_val + 1e-9


def test_engine_api_listing1(env):
    cfg, ds, params = env
    engine = alto.Engine(strategy="adapter_parallel", total_gpus=4)
    tasks = [alto.Task(model=cfg, dataset=ds, num_gpus=2, max_steps=10,
                       num_slots=2,
                       search_space={"lr": [1e-3, 3e-3],
                                     "batch_size": [2]}),
             alto.Task(model=cfg, dataset=ds, num_gpus=1, max_steps=10,
                       num_slots=2, name="task-b",
                       search_space={"lr": [1e-3], "rank": [4, 8]})]
    schedule = engine.schedule(tasks, method="cp")
    schedule.validate(4)
    report = engine.batched_execution(
        tasks, schedule, alto.EarlyExit(warmup_ratio=0.2, select_ratio=0.5))
    assert len(report.task_results) == 2
    for tr in report.task_results.values():
        assert np.isfinite(tr.best_val)


def test_slot_batcher_homogeneous_and_epochs():
    ds = make_task_dataset("t", 64, seq_len=8, num_train=10, num_val=4)
    b = SlotBatcher(ds, Z=3, per_adapter_batch=4, seed=0)
    toks, labels = b.next_batch()
    assert toks.shape == (3, 4, 8) and labels.shape == (3, 4, 8)
    np.testing.assert_array_equal(toks[:, :, 1:], labels[:, :, :-1])
    for _ in range(10):
        b.next_batch()
    assert all(e >= 2 for e in b.epochs)        # cycled epochs
    vt, vl = b.val_batch()
    np.testing.assert_array_equal(vt[0], vt[1])  # same val rows per slot


def test_all_jobs_diverge_returns_empty_winner(env):
    """Every job diverging (all best_vals non-finite) must yield a
    TaskResult with best_job=None / best_val=inf, not a crash."""
    cfg, ds, params = env
    ex = BatchedExecutor(cfg, params, ds, Z=2, per_adapter_batch=4,
                         ee=EarlyExitConfig(warmup_ratio=0.2,
                                            select_ratio=1.0),
                         eval_every=2, seed=0)
    jobs = {
        "boom1": TrainConfig(learning_rate=1e9, lora_rank=8, max_steps=10,
                             grad_clip=0.0),
        "boom2": TrainConfig(learning_rate=5e9, lora_rank=8, max_steps=10,
                             grad_clip=0.0),
    }
    res = ex.run_task("task", jobs, total_steps=10)
    assert res.best_job is None
    assert res.best_val == float("inf")
    for r in res.job_results.values():
        assert r.exit_reason is not None
        assert r.adapter is None


def test_backfill_wired_through_intra_task_policy(env, monkeypatch):
    """§A.3 wiring: continue-phase backfill must go through the
    sched/intra_task ExecutorSlots policy (memory-model token-budget
    admission — the same-batch-size fast path is dead now that slots are
    ragged), not a FIFO queue pop."""
    from repro.sched import intra_task

    calls = []
    orig = intra_task.ExecutorSlots.backfill

    def spy(self, queue):
        calls.append([j.job_id for j in queue])
        return orig(self, queue)

    monkeypatch.setattr(intra_task.ExecutorSlots, "backfill", spy)
    cfg, ds, params = env
    ex = BatchedExecutor(cfg, params, ds, Z=2, per_adapter_batch=4,
                         ee=EarlyExitConfig(warmup_ratio=0.25,
                                            select_ratio=1.0),
                         eval_every=2, seed=0)
    jobs = {f"j{i}": TrainConfig(learning_rate=1e-3, lora_rank=4,
                                 max_steps=8) for i in range(4)}
    res = ex.run_task("task", jobs, total_steps=8)
    # 4 kept jobs on 2 slots: completions vacate slots that the policy
    # (not a FIFO pop) backfills
    assert calls, "backfill bypassed the intra-task policy"
    assert all(r.steps_trained >= 8 for r in res.job_results.values())
