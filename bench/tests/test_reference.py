"""The float32 reference against the program's ``jnp`` path, on the CPU at
the rehearsal's small widths with float32 weights: logits of a forward
pass, and the per-slot losses and LoRA gradients of a training step, with
two adapters of different ranks on two slots. Both sides compute in
float32 and differ only in the order of their sums, so they agree to
about 1e-5 relative."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from bench import harness, weights
from bench.reference import decoder

RANKS = [8, 32]
TOL = 2e-4


def setup(config: str):
    spec = harness.rehearsal_spec(harness.load_json(
        harness.BENCH, "configs", config + ".json"))
    spec["torch_dtype"] = "float32"
    cfg = harness.model_config(spec, [], rehearse=True)
    params = weights.make_params(spec, 7)
    lora = weights.adapters(spec, RANKS, 7)
    g = np.random.default_rng(0)
    tokens = g.integers(0, spec["vocab_size"], (len(RANKS), 2, 24))
    return spec, cfg, params, lora, jnp.asarray(tokens, jnp.int32)


def rel(a, b) -> float:
    a, b = np.asarray(a, np.float64), np.asarray(b, np.float64)
    return float(np.linalg.norm(a - b) / np.linalg.norm(b))


@pytest.mark.parametrize("config", ["stablelm-3b", "granite-8b-l12"])
def test_forward_logits_match(config):
    from repro.core import lora as LORA
    from repro.models import model as M
    spec, cfg, params, lora, tokens = setup(config)
    with LORA.slot_ranks(jnp.asarray(RANKS, jnp.int32)):
        h, _, _ = M.forward(cfg, params, lora, tokens, remat=False)
        got = M._unembed(cfg, params, h)
    for z in range(len(RANKS)):
        ref = decoder.logits(spec, params, weights.slot(lora, z), tokens[z])
        assert rel(got[z], ref) < TOL


@pytest.mark.parametrize("config", ["stablelm-3b", "granite-8b-l12"])
def test_loss_and_lora_gradients_match(config):
    from repro.core import lora as LORA
    from repro.core import losses
    spec, cfg, params, lora, tokens = setup(config)
    labels = jnp.roll(tokens, -1, axis=-1).at[:, :, -1].set(-1)
    batch = {"tokens": tokens, "labels": labels}
    active = jnp.ones((len(RANKS),), jnp.int32)

    def total(l):
        with LORA.slot_ranks(jnp.asarray(RANKS, jnp.int32)):
            return losses.sft_loss(cfg, params, l, batch, active)

    (_, per_slot), grads = jax.value_and_grad(total, has_aux=True)(lora)
    for z in range(len(RANKS)):
        a = weights.slot(lora, z)
        ref, ref_g = jax.value_and_grad(
            lambda x: decoder.loss(spec, params, x, tokens[z], labels[z]))(a)
        assert float(per_slot[z]) == pytest.approx(float(ref), rel=TOL)
        for t in a:
            for m in ("A", "B"):
                assert rel(grads[t][m][:, z], ref_g[t][m]) < TOL, (t, m)


def test_fp8_control_departs():
    """The control's logits differ from the reference's by far more than
    the program's float32 path does."""
    spec, cfg, params, lora, tokens = setup("stablelm-3b")
    a = weights.slot(lora, 0)
    ref = decoder.logits(spec, params, a, tokens[0])
    q = decoder.logits(spec, params, a, tokens[0], "fp8")
    assert rel(q, ref) > 50 * TOL
