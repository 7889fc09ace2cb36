"""Tile-plan autotuner: candidate legality, tuned-vs-default bitwise
identity (fwd + VJP through the ops dispatch), winner persistence through
ProfileStore (including the atomic save round-trip)."""
import json
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.kernels.grouped_lora import autotune as AT
from repro.kernels.grouped_lora import ops
from repro.sched.profiler import ProfileStore

Z, T, DIN, DOUT, RMAX = 3, 24, 64, 48, 16


def _operands(seed=0):
    ks = jax.random.split(jax.random.PRNGKey(seed), 4)
    x = jax.random.normal(ks[0], (Z, T, DIN), jnp.float32)
    A = 0.1 * jax.random.normal(ks[1], (Z, DIN, RMAX), jnp.float32)
    B = 0.1 * jax.random.normal(ks[2], (Z, RMAX, DOUT), jnp.float32)
    dy = jax.random.normal(ks[3], (Z, T, DOUT), jnp.float32)
    scale = jnp.ones((Z,), jnp.float32)
    ranks = jnp.asarray([8, 16, 8], jnp.int32)
    rows = jnp.asarray([T, T // 2, T], jnp.int32)
    return x, A, B, dy, scale, ranks, rows


# ---------------------------------------------------------------------------
# candidate legality
# ---------------------------------------------------------------------------

def test_candidates_are_sublane_mxu_legal():
    Tp, dinp, doutp, _ = AT.padded_dims(T, DIN, DOUT, RMAX)
    plans = AT.candidate_plans(T, DIN, DOUT, RMAX, max_candidates=64)
    assert plans[0] == AT.DEFAULT_PLAN
    assert len(plans) > 1, "no non-default candidates for this shape"
    for p in plans[1:]:
        assert AT.is_legal(p, T, DIN, DOUT, RMAX), p
        # sublane units on token axes; the rank axis is never tiled
        assert p.bm % 8 == 0 and p.bt % 8 == 0, p
        # grid-exact: a block below a dim it tiles must divide it
        for block, dim in ((p.bm, Tp), (p.bt, Tp),
                           (p.bn, dinp), (p.bn, doutp),
                           (p.bk, dinp), (p.bk, doutp)):
            assert block >= dim or dim % block == 0, (p, block, dim)


def test_candidates_pin_contraction_blocks():
    # bitwise contract: bk/bt tile contraction dims, so candidates must
    # keep the default grouping (see autotune module docstring)
    for p in AT.candidate_plans(T, DIN, DOUT, RMAX, max_candidates=64):
        assert p.bk == AT.DEFAULT_PLAN.bk and p.bt == AT.DEFAULT_PLAN.bt, p


def test_illegal_plans_rejected():
    bad = [AT.TilePlan(bm=12),                 # not a sublane multiple
           AT.TilePlan(bt=4),                  # not a sublane multiple
           AT.TilePlan(bm=0),                  # non-positive
           AT.TilePlan(bm=16)]                 # 16 < Tp=24 and 24 % 16 != 0
    for p in bad:
        assert not AT.is_legal(p, T, DIN, DOUT, RMAX), p


def test_token_bucket_shares_plans_across_nearby_widths():
    assert AT.token_bucket(100) == AT.token_bucket(128) == 128
    assert AT.plan_key(DIN, DOUT, RMAX, Z, 100) == \
        AT.plan_key(DIN, DOUT, RMAX, Z, 128)
    assert AT.plan_key(DIN, DOUT, RMAX, Z, 129) != \
        AT.plan_key(DIN, DOUT, RMAX, Z, 128)


# ---------------------------------------------------------------------------
# tuned-vs-default bitwise identity (fwd + VJP)
# ---------------------------------------------------------------------------

def _fwd_vjp(plan):
    x, A, B, dy, scale, ranks, rows = _operands()

    def loss(x_, A_, B_):
        y = ops.ranklocal_grouped_lora(x_, A_, B_, scale, ranks, rows,
                                       interpret=True, plan=plan)
        return jnp.sum(y * dy), y

    (_, y), grads = jax.value_and_grad(loss, argnums=(0, 1, 2),
                                       has_aux=True)(x, A, B)
    return [np.asarray(y)] + [np.asarray(g) for g in grads]


def test_tuned_plan_bitwise_identical_fwd_and_vjp():
    tuned = [p for p in AT.candidate_plans(T, DIN, DOUT, RMAX,
                                           max_candidates=64)
             if p != AT.DEFAULT_PLAN]
    assert tuned, "shape produced no tuned candidates"
    base = _fwd_vjp(None)
    for plan in tuned[:4]:
        outs = _fwd_vjp(plan)
        for o, b in zip(outs, base):
            assert o.tobytes() == b.tobytes(), plan


def test_six_kernel_step_bitwise_across_candidates():
    # the sweep's own unit of comparison: all six rank-local kernels
    x, A, B, dy, scale, ranks, rows = _operands()
    args = (x, A, B, dy, scale, rows, ranks)
    base = [np.asarray(o) for o in
            AT.six_kernel_step(AT.DEFAULT_PLAN, interpret=True)(*args)]
    for plan in AT.candidate_plans(T, DIN, DOUT, RMAX,
                                   max_candidates=6)[1:]:
        outs = [np.asarray(o) for o in
                AT.six_kernel_step(plan, interpret=True)(*args)]
        for o, b in zip(outs, base):
            assert o.tobytes() == b.tobytes(), plan


def test_plan_threads_through_dense_and_ragged_dispatch():
    # full-rank dispatch routes to the dense/ragged paths — a tuned plan
    # must stay bitwise there too
    x, A, B, dy, scale, _, rows = _operands()
    full = jnp.full((Z,), RMAX, jnp.int32)
    plan = AT.TilePlan(bm=8, bn=128)
    for rows_arg in (None, rows):
        y0 = ops.ranklocal_grouped_lora(x, A, B, scale, full, rows_arg,
                                        interpret=True)
        y1 = ops.ranklocal_grouped_lora(x, A, B, scale, full, rows_arg,
                                        interpret=True, plan=plan)
        assert np.asarray(y0).tobytes() == np.asarray(y1).tobytes()


# ---------------------------------------------------------------------------
# sweep + winner persistence
# ---------------------------------------------------------------------------

def _tiny_sweep(**kw):
    return AT.sweep(DIN, DOUT, RMAX, Z=Z, tokens=T, interpret=True,
                    max_candidates=3, iters=1, repeats=1, **kw)


def test_sweep_winner_is_bitwise_and_not_slower_than_default():
    res = _tiny_sweep()
    assert res.best_s <= res.default_s + 1e-12
    winner = [c for c in res.candidates if c.plan == res.plan]
    assert winner and winner[0].bitwise_equal_default
    assert res.speedup >= 1.0
    assert res.flops > 0


def test_autotune_in_process_cache():
    AT.clear_plan_cache()
    p1 = AT.autotune_tile_plan(DIN, DOUT, RMAX, Z=Z, tokens=T,
                               interpret=True, max_candidates=3,
                               iters=1, repeats=1)
    assert AT.plan_key(DIN, DOUT, RMAX, Z, T) in AT._PLANS
    p2 = AT.autotune_tile_plan(DIN, DOUT, RMAX, Z=Z, tokens=T,
                               interpret=True)   # cache hit: no sweep args
    assert p1 == p2
    AT.clear_plan_cache()


def test_winner_persists_and_reloads_through_profile_store(tmp_path):
    store = ProfileStore()
    AT.clear_plan_cache()
    p1 = AT.autotune_tile_plan(DIN, DOUT, RMAX, Z=Z, tokens=T,
                               interpret=True, store=store,
                               max_candidates=3, iters=1, repeats=1)
    key = AT.plan_key(DIN, DOUT, RMAX, Z, T)
    assert AT.TilePlan.from_json(store.get_spec(key)) == p1
    # durable specs survive version bumps (observations do not evict them)
    store.record(("arch", 1), realized_duration=1.0, estimated_duration=2.0)
    assert store.get_spec(key) is not None

    path = tmp_path / "profile.json"
    store.save(str(path))
    reloaded = ProfileStore.load(str(path))
    AT.clear_plan_cache()
    # a fresh process with the reloaded store must NOT re-sweep: the
    # durable spec is the winner (iters/repeats absent would make a
    # sweep visible as a different plan only by accident, so check the
    # spec layer directly too)
    assert AT.TilePlan.from_json(reloaded.get_spec(key)) == p1
    p2 = AT.autotune_tile_plan(DIN, DOUT, RMAX, Z=Z, tokens=T,
                               interpret=True, store=reloaded)
    assert p2 == p1
    AT.clear_plan_cache()


def test_profile_store_save_is_atomic(tmp_path):
    # tmp-file + os.replace: no partial file is ever visible at `path`,
    # and a pre-existing good file survives a crashed writer (simulated
    # by the tmp file of a dead pid lying around)
    store = ProfileStore()
    store.put_spec(("tile_plan", 1, 2), {"bm": 8}, durable=True)
    path = tmp_path / "p.json"
    store.save(str(path))
    with open(path) as f:
        assert json.load(f)["durable_specs"]
    leftover = tmp_path / "p.json.tmp.99999"
    leftover.write_text("{corrupt")
    store.save(str(path))                   # replaces atomically, ignores it
    assert ProfileStore.load(str(path)).get_spec(
        ("tile_plan", 1, 2)) == {"bm": 8}
    assert os.path.exists(leftover)          # untouched: distinct pid suffix


def test_durable_spec_must_be_json():
    store = ProfileStore()
    with pytest.raises(TypeError):
        store.put_spec(("k",), object(), durable=True)
