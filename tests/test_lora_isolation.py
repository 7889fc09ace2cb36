"""ALTO's core soundness invariant: slot isolation.

Co-locating adapters on one backbone must not change any adapter's
gradients: slot z's grad depends only on slot z's data and params (the base
is frozen; the per-slot loss is a sum). This is what makes batched
multi-LoRA training equivalent to sequential training (paper §6.1) — and,
lifted one level, what makes CROSS-TASK co-location sound: two different
tasks' slots on one shared executor train exactly as each task would
alone (the executor-level tests at the bottom)."""

import functools

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs.base import TrainConfig
from repro.core import lora as LORA
from repro.core.adapter_state import SlotManager
from repro.core.early_exit import EarlyExitConfig
from repro.core.executor import (SharedBackboneExecutor, TaskLifecycle,
                                 run_colocated)
from repro.core.losses import sft_loss
from repro.data.synthetic import SlotBatcher, make_task_dataset
from repro.models import model as M
from repro.optim import adamw
from tests.conftest import reduced_f32


@pytest.fixture(scope="module")
def setup():
    cfg = reduced_f32("paper-llama-tiny", num_layers=2, d_model=128,
                      vocab=128)
    key = jax.random.PRNGKey(0)
    params = M.init_params(key, cfg)
    Z = 3
    ranks = jnp.array([4, 8, 8])
    lt = LORA.init_lora_tree(key, cfg, Z, ranks, M.target_shapes(cfg))
    # make B nonzero so the adapters matter
    lt = jax.tree_util.tree_map(
        lambda x: x + 0.01 * jax.random.normal(key, x.shape), lt)
    lt = LORA.mask_lora_tree(lt, ranks, cfg.lora.r_max)
    tokens = jax.random.randint(key, (Z, 2, 16), 0, cfg.vocab_size)
    return cfg, params, lt, ranks, tokens


def grads_of(cfg, params, lt, tokens, active):
    def f(lora_):
        total, _ = sft_loss(cfg, params, lora_,
                            {"tokens": tokens, "labels": tokens},
                            active, remat=False)
        return total
    return jax.grad(f)(lt)


def test_grad_isolation_across_slots(setup):
    """Changing slot 2's data / params leaves slot 0-1 grads bit-identical."""
    cfg, params, lt, ranks, tokens = setup
    active = jnp.ones((3,), jnp.int32)
    g1 = grads_of(cfg, params, lt, tokens, active)
    # perturb slot 2's data AND params
    tokens2 = tokens.at[2].set((tokens[2] + 17) % cfg.vocab_size)
    lt2 = jax.tree_util.tree_map(
        lambda x: x.at[:, 2].mul(1.7) if x.ndim >= 2 else x, lt)
    g2 = grads_of(cfg, params, lt2, tokens2, active)
    for t in g1:
        for m in ("A", "B"):
            np.testing.assert_array_equal(np.asarray(g1[t][m][:, :2]),
                                          np.asarray(g2[t][m][:, :2]))


def test_inactive_slot_gets_zero_grad(setup):
    cfg, params, lt, ranks, tokens = setup
    active = jnp.array([1, 0, 1], jnp.int32)
    g = grads_of(cfg, params, lt, tokens, active)
    for t in g:
        for m in ("A", "B"):
            assert float(jnp.abs(g[t][m][:, 1]).max()) == 0.0


def test_colocated_equals_solo(setup):
    """Slot-z loss when co-located == loss when trained alone (Z=1)."""
    cfg, params, lt, ranks, tokens = setup
    active = jnp.ones((3,), jnp.int32)
    _, per = sft_loss(cfg, params, lt,
                      {"tokens": tokens, "labels": tokens}, active,
                      remat=False)
    for z in range(3):
        solo_lt = jax.tree_util.tree_map(lambda x: x[:, z:z + 1], lt)
        _, per_solo = sft_loss(cfg, params, solo_lt,
                               {"tokens": tokens[z:z + 1],
                                "labels": tokens[z:z + 1]},
                               jnp.ones((1,), jnp.int32), remat=False)
        np.testing.assert_allclose(float(per[z]), float(per_solo[0]),
                                   rtol=1e-5, atol=1e-6)


def test_rank_mask_invariance(setup):
    """An adapter padded from r=4 to r_max behaves exactly like rank 4."""
    cfg, params, lt, ranks, tokens = setup
    active = jnp.ones((3,), jnp.int32)
    _, per1 = sft_loss(cfg, params, lt,
                       {"tokens": tokens, "labels": tokens}, active,
                       remat=False)
    # scribble garbage into the masked region; re-mask; loss unchanged
    lt_dirty = jax.tree_util.tree_map(lambda x: x + 100.0, lt)
    lt_clean = LORA.mask_lora_tree(lt_dirty, ranks, cfg.lora.r_max)
    lt_fixed = jax.tree_util.tree_map(
        lambda clean, orig, dirty: jnp.where(jnp.abs(clean - dirty) > 0,
                                             orig, clean),
        lt_clean, lt, lt_dirty)
    # only the masked region differs between lt and lt_fixed... rebuild:
    # masked(lt + 100) has masked region = 0 == masked(lt); unmasked differs.
    # Instead: verify that masking dirty params zeroes exactly the pad.
    r_max = cfg.lora.r_max
    for t, ab in lt_clean.items():
        for z, rk in enumerate([4, 8, 8]):
            if rk >= r_max:
                continue   # full-rank slot: no padded region to check
            assert float(jnp.abs(ab["A"][:, z, :, rk:]).max()) == 0.0
            assert float(jnp.abs(ab["B"][:, z, rk:, :]).max()) == 0.0


# ---------------------------------------------------------------------------
# Executor-level cross-TASK isolation (shared-backbone co-location)
# ---------------------------------------------------------------------------

@pytest.fixture(scope="module")
def exec_env():
    cfg = reduced_f32("paper-llama-tiny", num_layers=2, d_model=64,
                      vocab=128)
    params = M.init_params(jax.random.PRNGKey(0), cfg)
    ds_a = make_task_dataset("task-a", cfg.vocab_size, seq_len=16,
                             num_train=32, num_val=8, difficulty=0.2, seed=1)
    ds_b = make_task_dataset("task-b", cfg.vocab_size, seq_len=16,
                             num_train=32, num_val=8, difficulty=0.6, seed=2)
    return cfg, params, ds_a, ds_b


def _lifecycle(ex, name, ds, seed, total_steps=8, width=None,
               ranks=(4, 8)):
    kw = {} if width is None else {"per_adapter_batch": width}
    jobs = {f"{name}/j{i}": TrainConfig(learning_rate=lr, lora_rank=rk,
                                        max_steps=total_steps, **kw)
            for i, (lr, rk) in enumerate(zip((3e-3, 1e-3), ranks))}
    ee = EarlyExitConfig(warmup_ratio=0.25, select_ratio=1.0)
    return TaskLifecycle(
        ex, name, jobs, total_steps, ee=ee, max_slots=2,
        batcher=SlotBatcher(ds, 2, ex.b, seed=seed), seed=seed)


def _run(cfg, params, lifecycle_specs, b_cap=2):
    """Fresh Z=4 shared executor; run the given tasks co-located.
    ``lifecycle_specs`` entries are (name, ds, seed) or
    (name, ds, seed, width) — width is the per-job batch size (ragged
    slots: tasks may differ)."""
    ex = SharedBackboneExecutor(cfg, params, Z=4, per_adapter_batch=b_cap,
                                eval_every=2, seed=0)
    lcs = [_lifecycle(ex, spec[0], spec[1], spec[2],
                      width=(spec[3] if len(spec) > 3 else None))
           for spec in lifecycle_specs]
    results = run_colocated(ex, lcs)
    hists = {spec[0]: {j: (tuple(m.val_hist), tuple(m.raw_train_hist))
                       for j, m in lc.monitors.items()}
             for spec, lc in zip(lifecycle_specs, lcs)}
    return results, hists


def test_cross_task_losses_bitwise_equal_solo(exec_env):
    """Two DIFFERENT tasks co-located on one shared executor produce
    bitwise-identical train/val loss histories — and therefore identical
    best-val results — to each task running alone (the loss-isolation
    property across task boundaries)."""
    cfg, params, ds_a, ds_b = exec_env
    fused, fused_h = _run(cfg, params,
                          [("A", ds_a, 3), ("B", ds_b, 4)])
    solo_a, solo_a_h = _run(cfg, params, [("A", ds_a, 3)])
    solo_b, solo_b_h = _run(cfg, params, [("B", ds_b, 4)])
    assert fused_h["A"] == solo_a_h["A"]      # bitwise: tuples of floats
    assert fused_h["B"] == solo_b_h["B"]
    assert fused["A"].best_val == solo_a["A"].best_val
    assert fused["B"].best_val == solo_b["B"].best_val
    assert fused["A"].best_job == solo_a["A"].best_job
    assert fused["B"].best_job == solo_b["B"].best_job


def test_ragged_cross_task_losses_bitwise_equal_solo(exec_env):
    """Tasks with DIFFERENT per-adapter batch sizes fused on one shared
    executor (ragged slots: A trains b=2, B trains b=4 in the same fused
    step) produce bitwise-identical train/val loss histories to each task
    running alone on the same-capacity replica — the loss-isolation
    property survives width heterogeneity."""
    cfg, params, ds_a, ds_b = exec_env
    specs = [("A", ds_a, 3, 2), ("B", ds_b, 4, 4)]
    fused, fused_h = _run(cfg, params, specs, b_cap=4)
    solo_a, solo_a_h = _run(cfg, params, [specs[0]], b_cap=4)
    solo_b, solo_b_h = _run(cfg, params, [specs[1]], b_cap=4)
    assert fused_h["A"] == solo_a_h["A"]      # bitwise: tuples of floats
    assert fused_h["B"] == solo_b_h["B"]
    assert fused["A"].best_val == solo_a["A"].best_val
    assert fused["B"].best_val == solo_b["B"].best_val
    # the narrow task really trained at its own width
    for r in fused["A"].job_results.values():
        assert r.samples_trained == r.steps_trained * 2
    for r in fused["B"].job_results.values():
        assert r.samples_trained == r.steps_trained * 4


def test_ragged_full_width_host_unperturbed_by_narrow_guest(exec_env):
    """A full-width task flips from the dense dispatch (alone) to the
    ragged dispatch (narrow co-tenant present) — its losses must not
    move a bit either way."""
    cfg, params, ds_a, ds_b = exec_env
    fused, fused_h = _run(cfg, params,
                          [("A", ds_a, 3, 4), ("B", ds_b, 4, 2)], b_cap=4)
    solo, solo_h = _run(cfg, params, [("A", ds_a, 3, 4)], b_cap=4)
    assert fused_h["A"] == solo_h["A"]
    assert fused["A"].best_val == solo["A"].best_val


def test_ragged_mixed_seq_len_cross_task_bitwise(exec_env):
    """Tasks with DIFFERENT seq lens (16 vs 8) — and different widths —
    fused on one seq_cap=16 executor: the short-seq guest's lanes pad
    mid-row (label masking keeps it exact) and both tasks' loss
    histories stay bitwise identical to running alone."""
    cfg, params, ds_a, _ = exec_env
    ds_short = make_task_dataset("task-c", cfg.vocab_size, seq_len=8,
                                 num_train=32, num_val=8, difficulty=0.4,
                                 seed=5)

    def run(specs):
        ex = SharedBackboneExecutor(cfg, params, Z=4, per_adapter_batch=4,
                                    eval_every=2, seed=0, seq_cap=16)
        lcs = [_lifecycle(ex, name, ds, seed, width=w)
               for name, ds, seed, w in specs]
        results = run_colocated(ex, lcs)
        hists = {lc.task_name: {j: (tuple(m.val_hist),
                                    tuple(m.raw_train_hist))
                                for j, m in lc.monitors.items()}
                 for lc in lcs}
        return results, hists

    specs = [("A", ds_a, 3, 4), ("C", ds_short, 5, 2)]
    fused, fused_h = run(specs)
    solo_a, solo_a_h = run([specs[0]])
    solo_c, solo_c_h = run([specs[1]])
    assert fused_h["A"] == solo_a_h["A"]
    assert fused_h["C"] == solo_c_h["C"]
    assert fused["A"].best_val == solo_a["A"].best_val
    assert fused["C"].best_val == solo_c["C"].best_val
    assert np.isfinite(fused["C"].best_val)


def test_ragged_slot_widths_tracked(exec_env):
    """While mixed-width tasks are co-resident, SlotManager carries each
    slot's own (b, seq) and the executor's token accounting sums them."""
    cfg, params, ds_a, ds_b = exec_env
    ex = SharedBackboneExecutor(cfg, params, Z=4, per_adapter_batch=4,
                                eval_every=2, seed=0)
    lc_a = _lifecycle(ex, "A", ds_a, 3, width=2)
    lc_b = _lifecycle(ex, "B", ds_b, 4, width=4)
    ex.add_task(lc_a)
    ex.add_task(lc_b)
    lc_a.begin()
    lc_b.begin()
    widths = {ex.slots.slot_b[s] for _, s in lc_a.resident.values()}
    assert widths == {2}
    widths_b = {ex.slots.slot_b[s] for _, s in lc_b.resident.values()}
    assert widths_b == {4}
    seq = ds_a.train.shape[1] - 1
    assert ex.slots.occupied_tokens() == (2 + 2 + 4 + 4) * seq
    ex.run_steps(2)
    assert ex.take_tokens() == 2 * (2 + 2 + 4 + 4) * seq
    # per-slot token widths surface for ChunkReport observability
    assert sorted(ex.slot_token_widths()) == sorted(
        [2 * seq, 2 * seq, 4 * seq, 4 * seq])


def test_cross_task_slot_tags(exec_env):
    """While co-located, every occupied slot is tagged with its owning
    task and the executor attributes it correctly."""
    cfg, params, ds_a, ds_b = exec_env
    ex = SharedBackboneExecutor(cfg, params, Z=4, per_adapter_batch=2,
                                eval_every=2, seed=0)
    lc_a = _lifecycle(ex, "A", ds_a, 3)
    lc_b = _lifecycle(ex, "B", ds_b, 4)
    ex.add_task(lc_a)
    ex.add_task(lc_b)
    lc_a.begin()
    lc_b.begin()
    assert set(ex.slots.occupied_of("A").values()) == {0, 1}
    assert set(ex.slots.occupied_of("B").values()) == {2, 3}
    # per-task adapter addressing over (possibly non-contiguous) slots
    for task, lc in (("A", lc_a), ("B", lc_b)):
        adapters = ex.slots.adapters_of(task)
        assert set(adapters) == set(lc.jobs)
        for job, tree in adapters.items():
            _, slot = lc.resident[job]
            ref = ex.slots.adapter_at(slot)
            for t in ref:
                np.testing.assert_array_equal(tree[t]["A"], ref[t]["A"])
    ex.run_steps(2)
    for lc in (lc_a, lc_b):
        for mon in lc.monitors.values():
            assert mon.steps_trained == 2


# ---------------------------------------------------------------------------
# rank-local isolation (mixed TRUE ranks on one replica)
# ---------------------------------------------------------------------------

def _run_ranked(cfg, params, lifecycle_specs, b_cap=2):
    """Fresh Z=4 shared executor; specs are (name, ds, seed, ranks)."""
    ex = SharedBackboneExecutor(cfg, params, Z=4, per_adapter_batch=b_cap,
                                eval_every=2, seed=0)
    lcs = [_lifecycle(ex, name, ds, seed, ranks=ranks)
           for name, ds, seed, ranks in lifecycle_specs]
    results = run_colocated(ex, lcs)
    hists = {lc.task_name: {j: (tuple(m.val_hist), tuple(m.raw_train_hist))
                            for j, m in lc.monitors.items()}
             for lc in lcs}
    return results, hists


def test_ranklocal_cross_task_losses_bitwise_equal_solo(exec_env):
    """Tasks with DIFFERENT true ranks (2/4 vs full-rank 8/8 on an
    r_max=8 executor) co-located on one shared executor produce bitwise-
    identical loss histories to each task alone. The full-rank task flips
    from the no-binding dispatch (alone) to the rank-local dispatch
    (low-rank co-tenant present) — its losses must not move a bit."""
    cfg, params, ds_a, ds_b = exec_env
    assert cfg.lora.r_max == 8
    specs = [("A", ds_a, 3, (2, 4)), ("B", ds_b, 4, (8, 8))]
    fused, fused_h = _run_ranked(cfg, params, specs)
    solo_a, solo_a_h = _run_ranked(cfg, params, [specs[0]])
    solo_b, solo_b_h = _run_ranked(cfg, params, [specs[1]])
    assert fused_h["A"] == solo_a_h["A"]      # bitwise: tuples of floats
    assert fused_h["B"] == solo_b_h["B"]
    assert fused["A"].best_val == solo_a["A"].best_val
    assert fused["B"].best_val == solo_b["B"].best_val
    assert np.isfinite(fused["A"].best_val)


def test_ranklocal_ragged_rank_and_width_compose_bitwise(exec_env):
    """Mixed ranks AND mixed widths at once: a rank-2/b=2 guest next to a
    full-rank/b=4 host rides the composed rank-local x ragged path; both
    tasks' loss histories stay bitwise identical to solo."""
    cfg, params, ds_a, ds_b = exec_env

    def run(specs):
        ex = SharedBackboneExecutor(cfg, params, Z=4, per_adapter_batch=4,
                                    eval_every=2, seed=0)
        lcs = [_lifecycle(ex, name, ds, seed, width=w, ranks=ranks)
               for name, ds, seed, w, ranks in specs]
        results = run_colocated(ex, lcs)
        hists = {lc.task_name: {j: (tuple(m.val_hist),
                                    tuple(m.raw_train_hist))
                                for j, m in lc.monitors.items()}
                 for lc in lcs}
        return results, hists

    specs = [("A", ds_a, 3, 4, (8, 8)), ("B", ds_b, 4, 2, (2, 4))]
    fused, fused_h = run(specs)
    solo_a, solo_a_h = run([specs[0]])
    solo_b, solo_b_h = run([specs[1]])
    assert fused_h["A"] == solo_a_h["A"]
    assert fused_h["B"] == solo_b_h["B"]
    assert fused["A"].best_val == solo_a["A"].best_val
    assert fused["B"].best_val == solo_b["B"].best_val


def test_ranklocal_slot_ranks_tracked(exec_env):
    """While mixed-rank tasks are co-resident, SlotManager mirrors each
    slot's TRUE rank on host, the executor's rank-token accounting sums
    them, and ChunkReport-style observability surfaces the vector."""
    cfg, params, ds_a, ds_b = exec_env
    ex = SharedBackboneExecutor(cfg, params, Z=4, per_adapter_batch=2,
                                eval_every=2, seed=0)
    lc_a = _lifecycle(ex, "A", ds_a, 3, ranks=(2, 4))
    lc_b = _lifecycle(ex, "B", ds_b, 4, ranks=(8, 8))
    ex.add_task(lc_a)
    ex.add_task(lc_b)
    lc_a.begin()
    lc_b.begin()
    ranks_a = sorted(ex.slots.slot_rank[s] for _, s in
                     lc_a.resident.values())
    ranks_b = sorted(ex.slots.slot_rank[s] for _, s in
                     lc_b.resident.values())
    assert ranks_a == [2, 4] and ranks_b == [8, 8]
    assert ex.slots.mixed_rank(cfg.lora.r_max)
    seq = ds_a.train.shape[1] - 1
    assert ex.slots.occupied_rank_tokens() == 2 * seq * (2 + 4 + 8 + 8)
    assert sorted(ex.slot_rank_vector()) == [2, 4, 8, 8]
    # host mirror agrees with the device ranks the train step consumes
    np.testing.assert_array_equal(np.asarray(ex.slots.ranks),
                                  np.asarray(ex.slots.slot_rank))
    # rank bounds feed the §A.3 rank-token budget
    assert lc_a.rank_bound() == 4 and lc_b.rank_bound() == 8
    assert lc_a.rank_tokens_bound() == lc_a.tokens_bound() * 4
    ex.run_steps(2)
    for lc in (lc_a, lc_b):
        for mon in lc.monitors.values():
            assert mon.steps_trained == 2


# ---------------------------------------------------------------------------
# SlotSnapshot migration: suspend on one replica, resume on another
# ---------------------------------------------------------------------------

def _drive(ex, lcs, steps=None):
    """Minimal coordinator (what run_colocated does, but stoppable mid-run
    so a task can be suspended between boundaries)."""
    done = 0
    while any(not lc.done for lc in lcs):
        live = [lc for lc in lcs if not lc.done]
        n = max(min(min(lc.steps_until_boundary() for lc in live),
                    ex.eval_every), 1)
        ex.run_steps(n)
        for lc in live:
            lc.on_steps(n)
        done += n
        if steps is not None and done >= steps:
            return


def _hists(lc):
    return {j: (tuple(m.val_hist), tuple(m.raw_train_hist))
            for j, m in lc.monitors.items()}


def _serve_env_build(exec_env):
    """Adapters + prompts for the inference-path isolation tests."""
    cfg, params, _, _ = exec_env
    key = jax.random.PRNGKey(2)
    ranks = [4, 8, 2]
    stack = LORA.init_lora_tree(key, cfg, 3, jnp.asarray(ranks),
                                M.target_shapes(cfg))
    stack = jax.tree_util.tree_map(
        lambda x: x + 0.01 * jax.random.normal(key, x.shape), stack)
    stack = LORA.mask_lora_tree(stack, jnp.asarray(ranks), cfg.lora.r_max)
    adapters = {z: jax.tree_util.tree_map(lambda x: np.asarray(x[:, z]),
                                          stack) for z in range(3)}
    rng = np.random.default_rng(11)
    prompts = {z: [rng.integers(0, cfg.vocab_size, size=6).astype(np.int32)
                   for _ in range(2)] for z in range(3)}
    return cfg, params, adapters, ranks, prompts


@pytest.fixture(scope="module")
def serve_env(exec_env):
    return _serve_env_build(exec_env)


def _serve_run(cfg, params, adapters, ranks, prompts, publish,
               on_step=None):
    """One serving round on a Z=3 pool with the given slots published;
    returns per-request token streams + recorded per-step logits."""
    from repro.serve import AdapterPool, ServeRequest, ServingReplica
    pool = AdapterPool(cfg, 3)
    for z in publish:
        pool.publish(f"a{z}", adapters[z], ranks[z], slot=z)
    rep = ServingReplica(cfg, params, pool, lanes=2, max_len=24)
    reqs = [ServeRequest(f"r{z}{i}", f"a{z}", prompts[z][i], 8)
            for z in publish for i in range(2)]
    stats = rep.serve_round(
        reqs, on_step=(on_step(pool) if on_step else None),
        record_logits=True)
    return {r.request_id: tuple(r.tokens) for r in reqs}, stats.logits, pool


def test_fused_decode_bitwise_equal_solo(serve_env):
    """The training-side isolation invariant lifted to the INFERENCE path:
    N adapters fused on one serving replica produce, for every request,
    decode logits (and therefore greedy continuations) bitwise identical
    to serving that adapter alone on the same-capacity replica — the
    other slots' contents never leak into a request's stream."""
    cfg, params, adapters, ranks, prompts = serve_env
    fused_toks, fused_log, _ = _serve_run(cfg, params, adapters, ranks,
                                          prompts, publish=[0, 1, 2])
    for z in range(3):
        solo_toks, solo_log, _ = _serve_run(cfg, params, adapters, ranks,
                                            prompts, publish=[z])
        for i in range(2):
            assert fused_toks[f"r{z}{i}"] == solo_toks[f"r{z}{i}"]
        assert len(fused_log) == len(solo_log)
        for (tf, lf), (ts, ls) in zip(fused_log, solo_log):
            assert tf == ts
            np.testing.assert_array_equal(lf[z], ls[z])   # bitwise


def test_hot_publish_retire_mid_decode_leaves_residents_unchanged(serve_env):
    """Hot publish into a free slot (and retire of another slot) BETWEEN
    decode steps of an in-flight round: the resident requests' logits and
    token streams do not move a bit, and the pool ends with the expected
    adapter set — serving's slot-isolation counterpart of the training
    suspend/resume guarantees."""
    cfg, params, adapters, ranks, prompts = serve_env

    def hook(pool):
        def on_step(step):
            if step == 3:
                pool.publish("a1", adapters[1], ranks[1], slot=1)
            if step == 6:
                pool.retire("a1")
                pool.publish("a2", adapters[2], ranks[2], slot=2)
        return on_step

    base_toks, base_log, _ = _serve_run(cfg, params, adapters, ranks,
                                        prompts, publish=[0])
    hot_toks, hot_log, pool = _serve_run(cfg, params, adapters, ranks,
                                         prompts, publish=[0],
                                         on_step=hook)
    assert base_toks == hot_toks
    assert len(base_log) == len(hot_log)
    for (tb, lb), (th, lh) in zip(base_log, hot_log):
        assert tb == th
        np.testing.assert_array_equal(lb[0], lh[0])       # bitwise
    assert pool.resident() == {"a0": 0, "a2": 2}
    assert pool.version == 4        # 1 initial + hot publish/retire/publish


def test_mid_decode_join_leaves_residents_bitwise_unchanged(serve_env):
    """The continuous-batching isolation invariant: requests JOINING free
    lanes mid-decode (block prefill into their own lane caches — or, on a
    ring cache, a k_pos-reset streamed join — while residents keep
    decoding) must not move a resident lane's logits or tokens by a bit.
    Covers same-slot lane reuse AND other-slot joins, non-ring and ring."""
    from repro.serve import AdapterPool, ServeRequest, ServingReplica

    cfg, params, adapters, ranks, prompts = serve_env

    def run(join, ring):
        pool = AdapterPool(cfg, 3)
        for z in range(3):
            pool.publish(f"a{z}", adapters[z], ranks[z], slot=z)
        rep = ServingReplica(cfg, params, pool, lanes=2, max_len=24,
                             ring=ring)
        resident = ServeRequest("res", "a0", prompts[0][0], 10)
        assert rep.try_join(resident)
        for step in range(24):
            if join and step == 4:          # mid-decode, lanes still live
                for z, i in ((0, 1), (1, 0), (2, 1)):
                    r = ServeRequest(f"j{z}{i}", f"a{z}", prompts[z][i], 6)
                    assert rep.try_join(r)
            rep.step_continuous(record_logits=True)
            if resident.done:
                break
        assert resident.done
        return (tuple(resident.tokens),
                [(t, lg[0, 0]) for t, lg in rep.step_logits])

    for ring in (False, True):
        toks_solo, log_solo = run(join=False, ring=ring)
        toks_join, log_join = run(join=True, ring=ring)
        assert toks_solo == toks_join
        assert len(log_solo) == len(log_join)
        for (ts, ls), (tj, lj) in zip(log_solo, log_join):
            assert ts == tj
            np.testing.assert_array_equal(ls, lj)          # bitwise


def test_migration_across_replicas_bitwise_equal(exec_env):
    """The migration primitive end to end: a task mid-training on replica 1
    is suspended (SlotSnapshot per resident job), restored on replica 2
    that already hosts a DIFFERENT resident mix (so the physical slots
    differ), and trained to completion — its train/val loss histories and
    best-val result are bitwise identical to never migrating."""
    cfg, params, ds_a, ds_b = exec_env
    ds_c = make_task_dataset("task-c", cfg.vocab_size, seq_len=16,
                             num_train=32, num_val=8, difficulty=0.4,
                             seed=3)

    def make_ex():
        return SharedBackboneExecutor(cfg, params, Z=4, per_adapter_batch=2,
                                      eval_every=2, seed=0)

    # solo baseline: A never migrates (co-located with B throughout)
    ex0 = make_ex()
    a0 = _lifecycle(ex0, "A", ds_a, 3)
    b0 = _lifecycle(ex0, "B", ds_b, 4)
    run_colocated(ex0, [a0, b0])
    ref = _hists(a0)

    # migration run: A starts on replica 1 (with B), moves mid-continue to
    # replica 2 where C is already mid-flight on different physical slots
    ex1, ex2 = make_ex(), make_ex()
    A = _lifecycle(ex1, "A", ds_a, 3)
    B = _lifecycle(ex1, "B", ds_b, 4)
    C = _lifecycle(ex2, "C", ds_c, 5)
    ex2.add_task(C)
    C.begin()
    _drive(ex2, [C], steps=4)           # C occupies replica 2's low slots
    ex1.add_task(A)
    ex1.add_task(B)
    A.begin()
    B.begin()
    _drive(ex1, [A, B], steps=4)        # A mid-flight on replica 1
    slots_before = {j: s for j, (_, s) in A.resident.items()}
    A.suspend()
    assert ex2.can_admit_task(A)        # capacity check works while suspended
    A.resume(ex2)
    slots_after = {j: s for j, (_, s) in A.resident.items()}
    assert set(slots_before.values()) != set(slots_after.values())
    _drive(ex2, [A, C])
    _drive(ex1, [B])
    assert _hists(A) == ref             # bitwise: tuples of floats
    assert A.result().best_val == a0.result().best_val
    assert A.result().best_job == a0.result().best_job
    # the bystanders were untouched too
    assert np.isfinite(C.result().best_val)
    assert np.isfinite(B.result().best_val)


# ---------------------------------------------------------------------------
# Slot writes: admission, eviction and restore, each one donated program
# ---------------------------------------------------------------------------

def _init_loop(key, cfg, Z, ranks, shapes):
    """``init_lora_tree`` as a plain loop: one ``init_slot_lora`` a layer."""
    L, r = cfg.num_layers, cfg.lora.r_max
    targets = [t for t in cfg.lora.targets if t in shapes]
    keys = jax.random.split(key, len(targets) * L)
    tree = {}
    for i, t in enumerate(targets):
        pairs = [LORA.init_slot_lora(keys[i * L + l], *shapes[t], r, Z, ranks)
                 for l in range(L)]
        tree[t] = {"A": jnp.stack([a for a, _ in pairs]),
                   "B": jnp.stack([b for _, b in pairs])}
    return tree


def _bytes(tree):
    return [np.asarray(x).tobytes() for x in jax.tree_util.tree_leaves(tree)]


def _state(sm):
    """Every device array a SlotManager holds, copied to the host."""
    return jax.tree_util.tree_map(
        np.asarray, (sm.lora, sm.opt_state, sm.ranks, sm.active, sm.hp))


def _held(cfg, Z=3):
    """A manager whose every slot holds nonzero adapters, moments and step
    counts, as after training: a slot write must replace them all."""
    sm = SlotManager(cfg, Z, M.target_shapes(cfg), jax.random.PRNGKey(7))
    k = iter(jax.random.split(jax.random.PRNGKey(8), 64))

    def noisy(tree):
        return jax.tree_util.tree_map(
            lambda x: x + jax.random.normal(next(k), x.shape), tree)

    sm.lora = noisy(sm.lora)
    sm.opt_state = adamw.AdamWState(noisy(sm.opt_state.mu),
                                    noisy(sm.opt_state.nu),
                                    jnp.full((Z,), 5, jnp.int32))
    sm.ranks = jnp.full((Z,), 3, jnp.int32)
    sm.active = jnp.ones((Z,), jnp.int32)
    return sm


_TC = dict(learning_rate=3e-3, weight_decay=0.05, beta1=0.8, beta2=0.95,
           grad_clip=0.5)


@pytest.mark.parametrize("Z,ranks", [(1, [3]), (3, [2, 8, 0]), (2, [8, 8])])
def test_init_lora_tree_matches_per_layer_loop(exec_env, Z, ranks):
    """The vmapped init draws each layer's adapter from the same key as the
    per-layer loop, bit for bit, padded rank columns and zero ranks
    included."""
    cfg = exec_env[0]
    key = jax.random.PRNGKey(21)
    shapes = M.target_shapes(cfg)
    got = LORA.init_lora_tree(key, cfg, Z, jnp.asarray(ranks), shapes)
    ref = _init_loop(key, cfg, Z, jnp.asarray(ranks), shapes)
    assert list(got) == list(ref)
    assert _bytes(got) == _bytes(ref)


@pytest.mark.parametrize("slot,rank", [(0, 3), (2, 8), (1, 8)])
def test_admit_writes_slot_like_eager_reference(exec_env, slot, rank):
    """An admission leaves every array of the manager bitwise as the eager
    writes would: ``init_lora_tree``'s adapter put in with ``.at[:, slot]
    .set``, zeroed moments and count, the job's rank, active flag and
    hyperparameters; the other slots as they were. Ranks below and at
    r_max (8)."""
    cfg = exec_env[0]
    sm = _held(cfg)
    key = jax.random.PRNGKey(31)
    tc = TrainConfig(lora_rank=rank, **_TC)
    one = LORA.init_lora_tree(key, cfg, 1, jnp.asarray([rank]),
                              M.target_shapes(cfg))
    zero = functools.partial(jax.tree_util.tree_map,
                             lambda x: x.at[:, slot].set(0.0))
    hp = sm.hp
    ref = jax.tree_util.tree_map(np.asarray, (
        jax.tree_util.tree_map(lambda x, y: x.at[:, slot].set(y[:, 0]),
                               sm.lora, one),
        adamw.AdamWState(zero(sm.opt_state.mu), zero(sm.opt_state.nu),
                         sm.opt_state.count.at[slot].set(0)),
        sm.ranks.at[slot].set(rank), sm.active.at[slot].set(1),
        adamw.SlotHParams(
            hp.lr.at[slot].set(tc.learning_rate),
            hp.wd.at[slot].set(tc.weight_decay),
            hp.beta1.at[slot].set(tc.beta1), hp.beta2.at[slot].set(tc.beta2),
            hp.grad_clip.at[slot].set(tc.grad_clip))))
    sm.slot_jobs = [None] * sm.Z
    sm.admit(slot, "job", tc, key)
    assert _bytes(_state(sm)) == _bytes(ref)
    assert sm.slot_rank[slot] == rank and sm.slot_jobs[slot] == "job"


@pytest.mark.parametrize("slot,rank", [(0, 3), (2, 8)])
def test_evict_writes_positive_zeros(exec_env, slot, rank):
    """An eviction leaves every leaf of the slot exactly +0.0 (by bytes:
    no -0.0 from a rank mask), its count, rank and active flag 0, its
    hyperparameters and every other slot's bytes as they were."""
    cfg = exec_env[0]
    sm = _held(cfg)
    sm.slot_jobs = [None] * sm.Z
    sm.admit(slot, "job", TrainConfig(lora_rank=rank, **_TC),
             jax.random.PRNGKey(41))
    # the slot trains on: its adapter and moments move off the fresh ones
    sm.lora = jax.tree_util.tree_map(lambda x: x - 0.25, sm.lora)
    before = _state(sm)
    sm.evict(slot)
    after = _state(sm)
    for b, a in zip(
            jax.tree_util.tree_leaves((before[0], before[1].mu,
                                       before[1].nu)),
            jax.tree_util.tree_leaves((after[0], after[1].mu, after[1].nu))):
        assert a[:, slot].tobytes() == np.zeros_like(a[:, slot]).tobytes()
        others = [z for z in range(sm.Z) if z != slot]
        assert a[:, others].tobytes() == b[:, others].tobytes()
    for b, a in zip((before[1].count, before[2], before[3]),
                    (after[1].count, after[2], after[3])):
        assert a[slot] == 0
        assert np.delete(a, slot).tobytes() == np.delete(b, slot).tobytes()
    assert _bytes(after[4]) == _bytes(before[4])
    assert sm.slot_jobs[slot] is None and sm.slot_rank[slot] == 0


@pytest.mark.parametrize("src,dst", [(0, 2), (2, 1)])
def test_snapshot_restore_onto_another_slot_bitwise(exec_env, src, dst):
    """A snapshot restored onto another slot carries the adapter, moments,
    step count and rank over bit for bit, with the job's hyperparameters;
    the slots in between keep their bytes."""
    cfg = exec_env[0]
    sm = _held(cfg)
    sm.slot_jobs = [None] * sm.Z
    tc = TrainConfig(lora_rank=3, **_TC)
    sm.admit(src, "job", tc, jax.random.PRNGKey(51))
    sm.lora = jax.tree_util.tree_map(lambda x: x * 1.5 + 0.125, sm.lora)
    sm.opt_state = adamw.AdamWState(
        jax.tree_util.tree_map(lambda x: x + 0.5, sm.opt_state.mu),
        jax.tree_util.tree_map(lambda x: x + 2.0, sm.opt_state.nu),
        sm.opt_state.count.at[src].set(9))
    snap = sm.snapshot(src)
    sm.evict(src)
    before = _state(sm)
    sm.restore(dst, snap, tc)
    again = sm.snapshot(dst)
    assert _bytes((again.lora, again.mu, again.nu)) == \
        _bytes((snap.lora, snap.mu, snap.nu))
    assert (again.count, again.rank, again.job_id) == (9, 3, "job")
    after = _state(sm)
    assert after[3][dst] == 1
    np.testing.assert_array_equal(
        [h[dst] for h in after[4]],
        np.float32([tc.learning_rate, tc.weight_decay, tc.beta1, tc.beta2,
                    tc.grad_clip]))
    keep = [z for z in range(sm.Z) if z != dst]
    for b, a in zip(jax.tree_util.tree_leaves(before),
                    jax.tree_util.tree_leaves(after)):
        if a.ndim >= 2:
            assert a[:, keep].tobytes() == b[:, keep].tobytes()
        else:
            assert a[keep].tobytes() == b[keep].tobytes()


def test_slot_writes_compile_once(exec_env):
    """Once a manager has admitted and restored a job, admissions into
    other slots at other ranks, evictions and restores elsewhere lower no
    new program: slot and rank are traced, not static."""
    cfg = exec_env[0]
    sm = SlotManager(cfg, 3, M.target_shapes(cfg), jax.random.PRNGKey(7))
    keys = jax.random.split(jax.random.PRNGKey(61), 3)
    sm.admit(0, "a", TrainConfig(lora_rank=8, **_TC), keys[0])
    snap = sm.snapshot(0)
    sm.evict(0)
    sm.restore(1, snap, TrainConfig(lora_rank=8, **_TC))
    snap = sm.snapshot(1)
    jax.block_until_ready(sm.lora)
    lowered = []

    def listen(event, duration, **kw):
        if event == "/jax/core/compile/jaxpr_to_mlir_module_duration":
            lowered.append(kw.get("fun_name"))

    jax.monitoring.register_event_duration_secs_listener(listen)
    try:
        sm.admit(2, "b", TrainConfig(lora_rank=2, **_TC), keys[1])
        sm.evict(1)
        sm.admit(1, "c", TrainConfig(lora_rank=5, **_TC), keys[2])
        sm.evict(2)
        sm.restore(2, snap, TrainConfig(lora_rank=8, **_TC))
        jax.block_until_ready(sm.lora)
    finally:
        jax.monitoring.unregister_event_duration_listener(listen)
    assert lowered == []
    assert sm.slot_rank == [0, 5, 8] and list(np.asarray(sm.ranks)) == [0, 5, 8]


@pytest.mark.parametrize("write", ["admit", "evict", "restore"])
def test_slot_writes_leave_callers_adapters_readable(exec_env, write):
    """A slot write does not consume the adapter tree it replaces: an
    adapter tree taken before the write still reads as it did (only the
    optimizer state is donated)."""
    cfg = exec_env[0]
    sm = _held(cfg)
    sm.slot_jobs = [None] * sm.Z
    tc = TrainConfig(lora_rank=3, **_TC)
    sm.admit(0, "job", tc, jax.random.PRNGKey(71))
    snap = sm.snapshot(0)
    if write != "admit":
        sm.evict(0)
    taken = sm.lora
    before = _bytes(taken)
    if write == "admit":
        sm.admit(1, "next", tc, jax.random.PRNGKey(72))
    elif write == "evict":
        sm.admit(1, "next", tc, jax.random.PRNGKey(72))
        taken, before = sm.lora, _bytes(sm.lora)
        sm.evict(1)
    else:
        sm.restore(2, snap, tc)
    assert _bytes(taken) == before
    assert _bytes(sm.lora) != before


def test_admission_follows_replaced_optimizer_reset(exec_env, monkeypatch):
    """The admission program runs ``adamw.reset_slot`` as it stands at the
    call, not as it stood when the program was first traced: replaced by
    one that keeps the state, an admission into a trained slot keeps its
    moments and count; put back, the next admission zeroes them."""
    cfg = exec_env[0]
    sm = _held(cfg)
    sm.slot_jobs = [None] * sm.Z
    tc = TrainConfig(lora_rank=3, **_TC)
    sm.admit(0, "first", tc, jax.random.PRNGKey(81))   # traced as it is
    kept = _bytes((sm.opt_state.mu, sm.opt_state.nu))
    monkeypatch.setattr(adamw, "reset_slot", lambda state, slot: state)
    sm.admit(1, "stale", tc, jax.random.PRNGKey(82))
    assert _bytes((sm.opt_state.mu, sm.opt_state.nu)) == kept
    assert int(sm.opt_state.count[1]) == 5
    monkeypatch.undo()
    sm.evict(1)
    mu = np.asarray(jax.tree_util.tree_leaves(sm.opt_state.mu)[0])
    assert not mu[:, 1].any() and int(sm.opt_state.count[1]) == 0
