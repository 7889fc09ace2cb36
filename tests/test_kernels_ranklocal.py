"""Rank-local grouped-LoRA kernel parity vs the masked-jnp oracle.

The rank-local path (per-slot TRUE ranks masked inside the kernels)
must be EXACT: the padded rank region
contributes nothing to any output and receives exactly zero gradient —
even when it holds garbage — and concrete full-rank calls reproduce the
dense kernels bitwise. Interpret mode on CPU is the CI harness.
"""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import lora as L
from repro.kernels.grouped_lora import ops, ref
from repro.kernels.grouped_lora import ranklocal as RL


def make(Z, T, din, r, dout, dtype=jnp.float32, with_base=True, seed=0):
    ks = jax.random.split(jax.random.PRNGKey(seed), 5)
    x = jax.random.normal(ks[0], (Z, T, din), dtype)
    A = (0.1 * jax.random.normal(ks[1], (Z, din, r), jnp.float32)
         ).astype(dtype)
    B = (0.1 * jax.random.normal(ks[2], (Z, r, dout), jnp.float32)
         ).astype(dtype)
    scale = jnp.linspace(0.5, 2.0, Z)
    yb = (jax.random.normal(ks[3], (Z, T, dout), dtype)
          if with_base else None)
    return x, A, B, scale, yb


def dirty_pads(A, B, ranks):
    """Scribble garbage into the padded rank region — the rank-local path
    must mask it on load, so outputs cannot depend on it."""
    r = A.shape[2]
    keep = jnp.arange(r)[None, :] < jnp.asarray(ranks)[:, None]
    Ad = jnp.where(keep[:, None, :], A, 99.0)
    Bd = jnp.where(keep[:, :, None], B, -55.0)
    return Ad, Bd


# (Z, T, din, r, dout, ranks): aligned / odd shapes, rank-1, dead slots
CASES = [
    (1, 128, 256, 16, 256, (16,)),             # full (dense-degenerate)
    (2, 64, 96, 16, 80, (4, 11)),              # partial, odd boundary
    (3, 100, 130, 24, 200, (24, 1, 9)),        # rank-1 slot in the middle
    (4, 256, 512, 64, 512, (64, 32, 8, 4)),    # the rank-sweep mix
    (2, 7, 33, 5, 17, (1, 3)),                 # tiny unaligned everything
    (3, 40, 64, 8, 48, (0, 0, 0)),             # all slots rank-0 (dead)
]


@pytest.mark.parametrize("case", CASES)
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
@pytest.mark.parametrize("with_base", [True, False])
def test_ranklocal_forward_matches_ref(case, dtype, with_base):
    Z, T, din, r, dout, ranks = case
    x, A, B, scale, yb = make(Z, T, din, r, dout, dtype, with_base)
    ranks = jnp.asarray(ranks, jnp.int32)
    got = ops.ranklocal_grouped_lora(x, A, B, scale, ranks, None, yb,
                                     interpret=True)
    want = ref.ranklocal_lora_ref(x, A, B, scale, ranks, None, yb)
    tol = 1e-5 if dtype == jnp.float32 else 3e-2
    np.testing.assert_allclose(np.asarray(got, np.float32),
                               np.asarray(want, np.float32),
                               rtol=tol, atol=tol)


@pytest.mark.parametrize("case", CASES[1:4])
def test_ranklocal_gradients_match_ref(case):
    Z, T, din, r, dout, ranks = case
    x, A, B, scale, yb = make(Z, T, din, r, dout, jnp.float32, True)
    ranks = jnp.asarray(ranks, jnp.int32)

    def loss_k(x, A, B, yb):
        return jnp.sum(jnp.tanh(ops.ranklocal_grouped_lora(
            x, A, B, scale, ranks, None, yb, interpret=True)))

    def loss_r(x, A, B, yb):
        return jnp.sum(jnp.tanh(ref.ranklocal_lora_ref(
            x, A, B, scale, ranks, None, yb)))

    gk = jax.grad(loss_k, argnums=(0, 1, 2, 3))(x, A, B, yb)
    gr = jax.grad(loss_r, argnums=(0, 1, 2, 3))(x, A, B, yb)
    for a, b, name in zip(gk, gr, ["dx", "dA", "dB", "dyb"]):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                   rtol=2e-4, atol=2e-4, err_msg=name)


def test_padded_rank_region_ignored_and_zero_grad():
    """Garbage beyond ranks[z] must not leak into any output, and the
    padded region's gradient must be EXACTLY zero (dead tiles never
    accumulate) — the invariant that makes the optimizer re-mask
    redundant on this path."""
    Z, T, din, r, dout = 3, 32, 64, 16, 48
    x, A, B, scale, yb = make(Z, T, din, r, dout)
    ranks = jnp.asarray([4, 16, 9], jnp.int32)
    Ad, Bd = dirty_pads(A, B, ranks)
    got = ops.ranklocal_grouped_lora(x, Ad, Bd, scale, ranks, None, yb,
                                     interpret=True)
    clean = ops.ranklocal_grouped_lora(x, A, B, scale, ranks, None, yb,
                                       interpret=True)
    np.testing.assert_array_equal(np.asarray(got), np.asarray(clean))

    def loss(A_, B_):
        return jnp.sum(ops.ranklocal_grouped_lora(
            x, A_, B_, scale, ranks, None, interpret=True) ** 2)

    dA_, dB_ = jax.grad(loss, argnums=(0, 1))(Ad, Bd)
    for z, rk in enumerate([4, 16, 9]):
        if rk >= r:
            continue
        assert float(jnp.abs(dA_[z, :, rk:]).max()) == 0.0
        assert float(jnp.abs(dB_[z, rk:, :]).max()) == 0.0
    # valid region matches the oracle on the dirty params
    want = jax.grad(
        lambda A_, B_: jnp.sum(ref.ranklocal_lora_ref(
            x, A_, B_, scale, ranks) ** 2), argnums=(0, 1))(Ad, Bd)
    np.testing.assert_allclose(np.asarray(dA_), np.asarray(want[0]),
                               rtol=2e-4, atol=2e-4)
    np.testing.assert_allclose(np.asarray(dB_), np.asarray(want[1]),
                               rtol=2e-4, atol=2e-4)


def test_full_rank_bitwise_equal_dense():
    """Concrete ranks == r_max everywhere must reproduce the dense kernels
    bitwise — the executor's per-step rank dispatch relies on it."""
    Z, T, din, r, dout = 3, 64, 96, 8, 80
    x, A, B, scale, yb = make(Z, T, din, r, dout)
    full = jnp.full((Z,), r, jnp.int32)
    d = ops.grouped_lora(x, A, B, scale, yb, interpret=True)
    rl = ops.ranklocal_grouped_lora(x, A, B, scale, full, None, yb,
                                    interpret=True)
    np.testing.assert_array_equal(np.asarray(d), np.asarray(rl))
    # ... and with rows, the ragged path bitwise
    rows = jnp.asarray([64, 30, 0], jnp.int32)
    rg = ops.ragged_grouped_lora(x, A, B, scale, rows, yb, interpret=True)
    rl2 = ops.ranklocal_grouped_lora(x, A, B, scale, full, rows, yb,
                                     interpret=True)
    np.testing.assert_array_equal(np.asarray(rg), np.asarray(rl2))


def test_rank_one_degenerate():
    """rank-1 slots: the narrowest possible adapter, masked to a single
    column — must match the oracle and leave columns >= 1 at exactly
    zero gradient."""
    Z, T, din, r, dout = 2, 40, 64, 8, 48
    x, A, B, scale, yb = make(Z, T, din, r, dout)
    ranks = jnp.asarray([1, 1], jnp.int32)
    got = ops.ranklocal_grouped_lora(x, A, B, scale, ranks, None, yb,
                                     interpret=True)
    want = ref.ranklocal_lora_ref(x, A, B, scale, ranks, None, yb)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               rtol=1e-5, atol=1e-5)
    dA_ = jax.grad(lambda A_: jnp.sum(ops.ranklocal_grouped_lora(
        x, A_, B, scale, ranks, None, interpret=True) ** 2))(A)
    assert float(jnp.abs(dA_[:, :, 1:]).max()) == 0.0
    assert float(jnp.abs(dA_[:, :, :1]).max()) > 0.0


def test_ragged_rows_times_ranks_composition():
    """Both prefetch vectors live: slot z computes over only its first
    rows[z] token rows AND its first ranks[z] rank columns; fwd and VJP
    match the doubly-masked oracle, pads exactly zero on both axes."""
    Z, T, din, r, dout = 3, 48, 96, 16, 64
    x, A, B, scale, yb = make(Z, T, din, r, dout)
    ranks = jnp.asarray([4, 16, 7], jnp.int32)
    rows = jnp.asarray([48, 20, 0], jnp.int32)
    Ad, Bd = dirty_pads(A, B, ranks)
    got = ops.ranklocal_grouped_lora(x, Ad, Bd, scale, ranks, rows, yb,
                                     interpret=True)
    want = ref.ranklocal_lora_ref(x, Ad, Bd, scale, ranks, rows, yb)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               rtol=1e-5, atol=1e-5)
    # padded token rows: y_base passthrough
    np.testing.assert_array_equal(np.asarray(got[1, 20:]),
                                  np.asarray(yb[1, 20:]))
    np.testing.assert_array_equal(np.asarray(got[2]), np.asarray(yb[2]))

    def loss_k(x_, A_, B_, yb_):
        return jnp.sum(jnp.tanh(ops.ranklocal_grouped_lora(
            x_, A_, B_, scale, ranks, rows, yb_, interpret=True)))

    def loss_r(x_, A_, B_, yb_):
        return jnp.sum(jnp.tanh(ref.ranklocal_lora_ref(
            x_, A_, B_, scale, ranks, rows, yb_)))

    gk = jax.grad(loss_k, argnums=(0, 1, 2, 3))(x, Ad, Bd, yb)
    gr = jax.grad(loss_r, argnums=(0, 1, 2, 3))(x, Ad, Bd, yb)
    for a, b, name in zip(gk, gr, ["dx", "dA", "dB", "dyb"]):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                   rtol=2e-4, atol=2e-4, err_msg=name)
    # rank pads zero grad; row pads zero dX
    assert float(jnp.abs(gk[1][0, :, 4:]).max()) == 0.0
    assert float(jnp.abs(gk[2][2, 7:, :]).max()) == 0.0
    assert float(jnp.abs(gk[0][1, 20:]).max()) == 0.0


def test_individual_ranklocal_kernels_match_masked_einsum():
    Z, T, din, r, dout = 2, 128, 256, 16, 128
    x, A, B, scale, yb = make(Z, T, din, r, dout)
    ranks = jnp.asarray([16, 5], jnp.int32)
    rows = jnp.asarray([128, 37], jnp.int32)
    Am = ref._ranks_mask_A(A, ranks)
    Bm = ref._ranks_mask_B(B, ranks)
    xm = ref._rows_mask(x, rows)
    s = RL.xa(x, A, rows, ranks, interpret=True)
    np.testing.assert_allclose(np.asarray(s),
                               np.asarray(ref.grouped_xa_ref(xm, Am)),
                               rtol=1e-5, atol=1e-5)
    dy = yb
    dym = ref._rows_mask(dy, rows)
    ds_ = RL.ds(dy, B, scale, rows, ranks, interpret=True)
    want_ds = jnp.einsum("zto,zro->ztr", dym * scale[:, None, None], Bm)
    np.testing.assert_allclose(np.asarray(ds_), np.asarray(want_ds),
                               rtol=1e-5, atol=1e-5)
    dx_ = RL.dx(ds_, A, rows, ranks, interpret=True)
    np.testing.assert_allclose(
        np.asarray(dx_), np.asarray(jnp.einsum("ztr,zdr->ztd", ds_, Am)),
        rtol=1e-5, atol=1e-5)
    da_ = RL.da(x, ds_, rows, ranks, interpret=True)
    np.testing.assert_allclose(
        np.asarray(da_),
        np.asarray(ref._ranks_mask_A(
            jnp.einsum("ztd,ztr->zdr", xm, ds_), ranks)),
        rtol=1e-4, atol=1e-4)
    db_ = RL.db(s, dy, scale, rows, ranks, interpret=True)
    want_db = ref._ranks_mask_B(
        jnp.einsum("ztr,zto->zro", s, dym * scale[:, None, None]), ranks)
    np.testing.assert_allclose(np.asarray(db_), np.asarray(want_db),
                               rtol=1e-4, atol=1e-4)


def test_lora_delta_slot_ranks_dispatch():
    """core.lora: a slot_ranks binding routes lora_delta through the
    rank-local path on every backend — jnp masks A/B, pallas rides the
    rank-local kernels — and the two agree; composition with ragged_rows
    masks both axes."""
    Z, b, S, din, r, dout = 2, 4, 8, 32, 8, 24
    ks = jax.random.split(jax.random.PRNGKey(1), 3)
    x = jax.random.normal(ks[0], (Z, b, S, din))
    A = 0.1 * jax.random.normal(ks[1], (Z, din, r))
    B = 0.1 * jax.random.normal(ks[2], (Z, r, dout))
    scale = jnp.asarray([2.0, 0.5])
    ranks = jnp.asarray([3, 8], jnp.int32)
    Ad, Bd = dirty_pads(A, B, ranks)
    rows = jnp.asarray([b * S, 2 * S], jnp.int32)
    with L.slot_ranks(ranks):
        y_jnp = L.lora_delta(x, Ad, Bd, scale)
        with L.backend("pallas_interpret"):
            y_pal = L.lora_delta(x, Ad, Bd, scale)
        with L.ragged_rows(rows):
            y_jnp2 = L.lora_delta(x, Ad, Bd, scale)
            with L.backend("pallas_interpret"):
                y_pal2 = L.lora_delta(x, Ad, Bd, scale)
    np.testing.assert_allclose(np.asarray(y_jnp), np.asarray(y_pal),
                               rtol=1e-5, atol=1e-5)
    np.testing.assert_allclose(np.asarray(y_jnp2), np.asarray(y_pal2),
                               rtol=1e-5, atol=1e-5)
    # garbage pads ignored under the binding: clean params, same delta
    with L.slot_ranks(ranks):
        y_clean = L.lora_delta(x, A, B, scale)
    np.testing.assert_array_equal(np.asarray(y_jnp), np.asarray(y_clean))
    # row pads zero on the composed path
    assert float(jnp.abs(y_jnp2[1, 2:]).max()) == 0.0
    # without the binding the jnp path USES the garbage pads (dense math)
    y_dense = L.lora_delta(x, Ad, Bd, scale)
    assert float(jnp.abs(np.asarray(y_dense) - np.asarray(y_jnp)).max()) > 0


def test_train_step_pad_region_stays_zero_without_remask():
    """Pallas-path train-step invariant: with slot_ranks bound, the
    padded rank region of A/B (and the optimizer moments) stays EXACTLY
    zero across AdamW steps with NO rank re-mask — the gradient there is
    structurally zero (dead tiles), so mask_lora_tree is redundant on
    this path."""
    from repro.core.losses import sft_loss
    from repro.models import model as M
    from repro.optim import adamw
    from tests.conftest import reduced_f32

    cfg = reduced_f32("paper-llama-tiny", num_layers=2, d_model=64,
                      vocab=128)
    r_max = cfg.lora.r_max
    Z = 2
    ranks = jnp.asarray([2, r_max], jnp.int32)
    key = jax.random.PRNGKey(0)
    params = M.init_params(key, cfg)
    lt = L.init_lora_tree(key, cfg, Z, ranks, M.target_shapes(cfg))
    # nonzero B within the true rank so gradients actually flow
    m = L.rank_mask(ranks, r_max)

    def warm(t, is_A):
        bump = 0.01 * (m[None, :, None, :] if is_A else m[None, :, :, None])
        return t + bump
    lt = {t: {"A": warm(ab["A"], True), "B": warm(ab["B"], False)}
          for t, ab in lt.items()}
    opt = adamw.init_state(lt, Z)
    hp = adamw.SlotHParams.broadcast(Z, lr=1e-2, wd=0.01)
    active = jnp.ones((Z,), jnp.int32)
    tokens = jax.random.randint(key, (Z, 2, 8), 0, cfg.vocab_size)
    batch = {"tokens": tokens, "labels": tokens}

    def loss(lora_):
        return sft_loss(cfg, params, lora_, batch, active, remat=False)[0]

    for _ in range(2):
        with L.backend("pallas_interpret"), L.slot_ranks(ranks):
            grads = jax.grad(loss)(lt)
        for t in grads:
            assert float(jnp.abs(grads[t]["A"][:, 0, :, 2:]).max()) == 0.0
            assert float(jnp.abs(grads[t]["B"][:, 0, 2:, :]).max()) == 0.0
        # NO rank_masker: the re-mask the rank-local path makes redundant
        lt, opt = adamw.apply_updates(lt, grads, opt, hp, active,
                                      rank_masker=None)
    for t in lt:
        assert float(jnp.abs(lt[t]["A"][:, 0, :, 2:]).max()) == 0.0
        assert float(jnp.abs(lt[t]["B"][:, 0, 2:, :]).max()) == 0.0
        assert float(jnp.abs(opt.mu[t]["A"][:, 0, :, 2:]).max()) == 0.0
        assert float(jnp.abs(opt.nu[t]["B"][:, 0, 2:, :]).max()) == 0.0
    # the adapters did train inside the true rank region
    assert any(float(jnp.abs(lt[t]["A"][:, 0, :, :2]).max()) > 0
               for t in lt)
