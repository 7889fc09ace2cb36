"""Compile the main path's kernels and train step for a described TPU v5e.

No chip is needed: the TPU compiler in libtpu compiles for a topology that
is described, not attached, and refuses what the chip would refuse —
Mosaic block shapes that are not (8, 128)-tiled, kernels over their VMEM
budget, and programs that do not fit HBM (RESOURCE_EXHAUSTED). Nothing
runs, so these tests say nothing about values or times.

The topology is described inside a module-scoped fixture, never at import:
only one process may hold libtpu, and every pytest-xdist worker imports
this file.
"""
import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from repro.configs.registry import get_arch

Z, T = 4, 2048                      # slots, tokens per slot (b=4 x S=512)
STABLELM = get_arch("stablelm-3b")
R_MAX = STABLELM.lora.r_max


@pytest.fixture(scope="module")
def topo():
    from jax.experimental import topologies
    try:
        return topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")


@pytest.fixture(scope="module")
def one_chip(topo):
    return SingleDeviceSharding(topo.devices[0])


def _sds(one_chip, shape, dtype=jnp.bfloat16):
    return jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)


def _compile(fn, *args):
    compiled = jax.jit(fn).lower(*args).compile()
    assert "tpu_custom_call" in compiled.as_text(), "no Mosaic kernel"
    return compiled


@pytest.mark.parametrize("variant", ["dense", "ragged", "ranklocal"])
def test_grouped_lora_vjp_compiles(one_chip, variant):
    """Forward + the four backward kernels at stablelm-3b widths (the
    attention 2560->2560 and MLP 2560->6912 / 6912->2560 projections)."""
    from repro.kernels.grouped_lora import ops
    vec = _sds(one_chip, (Z,), jnp.int32)
    for din, dout in ((2560, 2560), (2560, 6912), (6912, 2560)):
        x = _sds(one_chip, (Z, T, din))
        A = _sds(one_chip, (Z, din, R_MAX), jnp.float32)
        B = _sds(one_chip, (Z, R_MAX, dout), jnp.float32)
        scale = _sds(one_chip, (Z,), jnp.float32)

        def loss(x, A, B, scale, rows, ranks):
            if variant == "dense":
                y = ops.grouped_lora(x, A, B, scale)
            elif variant == "ragged":
                y = ops.ragged_grouped_lora(x, A, B, scale, rows)
            else:
                y = ops.ranklocal_grouped_lora(x, A, B, scale, ranks,
                                               rows=rows)
            return jnp.sum(y.astype(jnp.float32))

        _compile(jax.grad(loss, argnums=(0, 1, 2)), x, A, B, scale, vec, vec)


def test_flash_attention_forward_compiles_head_dim_80(one_chip):
    from repro.kernels.flash_attention import ops as FA
    H, S, hd = STABLELM.num_heads, 512, STABLELM.resolved_head_dim
    assert hd == 80
    q = _sds(one_chip, (2 * H, S, hd))
    _compile(lambda q, k, v: FA.flash_attention(q, k, v, causal=True,
                                                bq=256, bk=512), q, q, q)


def test_linear_scan_compiles_at_rwkv6_widths(one_chip):
    from repro.kernels.linear_scan import ops as LS
    cfg = get_arch("rwkv6-3b")
    B, S, K = 2 * cfg.num_heads, 512, cfg.ssm.head_size
    q = _sds(one_chip, (B, S, K), jnp.float32)
    bonus = _sds(one_chip, (B, K), jnp.float32)
    _compile(lambda q, k, v, w, u: LS.linear_scan(
        q, k, v, w, bonus=u, chunk=cfg.ssm.chunk_size), q, q, q, q, bonus)


def test_stablelm_train_step_fits_at_picked_slots(one_chip):
    """The executor's donated train step, mixed ranks on the rank-local
    kernels, at the Z the engine's memory model picks for the chip smoke
    run's task; a step that does not fit fails to compile."""
    from repro.core import lora as LORA
    from repro.core import steps as STEPS
    from repro.core.engine import Engine, Task
    from repro.data.synthetic import make_task_dataset
    from repro.models import backend as MB
    from repro.models import model as M
    from repro.optim import adamw

    cfg = STABLELM
    b, S = 4, 512
    task = Task(model=cfg, name="compile", num_slots=0,
                dataset=make_task_dataset("compile", cfg.vocab_size,
                                          seq_len=S, num_train=8, num_val=4),
                search_space={"rank": [8, 16, 64], "lr": [1e-4, 1e-3],
                              "batch_size": [b]})
    z = Engine(total_gpus=1).pick_slots(task)
    assert 1 <= z <= 3

    def sds(tree):
        return jax.tree_util.tree_map(
            lambda x: _sds(one_chip, x.shape, x.dtype), tree)

    def adapters():
        return LORA.init_lora_tree(jax.random.PRNGKey(0), cfg, z,
                                   jnp.zeros((z,), jnp.int32),
                                   M.target_shapes(cfg))

    params = sds(jax.eval_shape(
        lambda: M.init_params(jax.random.PRNGKey(0), cfg)))
    lora = sds(jax.eval_shape(adapters))
    opt = sds(jax.eval_shape(lambda: adamw.init_state(adapters(), z)))
    hp = sds(jax.eval_shape(lambda: adamw.SlotHParams.broadcast(z)))
    vec = _sds(one_chip, (z,), jnp.int32)
    tok = _sds(one_chip, (z, b, S), jnp.int32)
    batch = {"tokens": tok, "labels": tok, "slot_ranks": vec}
    with LORA.backend("pallas"), MB.backend("pallas"):
        compiled = STEPS.jit_train_step(cfg).lower(
            params, lora, opt, hp, vec, vec, batch).compile()
    assert "tpu_custom_call" in compiled.as_text()
    mem = compiled.memory_analysis()
    # the adapter state is donated: the step's outputs alias its inputs
    state = sum(x.size * x.dtype.itemsize
                for x in jax.tree_util.tree_leaves((lora, opt)))
    assert mem.alias_size_in_bytes >= 0.99 * state


@pytest.mark.parametrize("write", ["admit", "restore"])
def test_stablelm_slot_writes_in_place(one_chip, write):
    """``SlotManager``'s admission (and eviction) program and its restore
    program at stablelm-3b widths, Z=2: the donated optimizer state aliases
    the output, so a slot write copies no moment leaf."""
    from repro.core import adapter_state as AS
    from repro.core import lora as LORA
    from repro.models import model as M
    from repro.optim import adamw

    cfg, z = STABLELM, 2
    shapes = M.target_shapes(cfg)

    def sds(tree):
        return jax.tree_util.tree_map(
            lambda x: _sds(one_chip, x.shape, x.dtype), tree)

    def adapters(n):
        return LORA.init_lora_tree(jax.random.PRNGKey(0), cfg, n,
                                   jnp.zeros((n,), jnp.int32), shapes)

    lora = sds(jax.eval_shape(lambda: adapters(z)))
    opt = sds(jax.eval_shape(lambda: adamw.init_state(adapters(z), z)))
    vec = _sds(one_chip, (z,), jnp.int32)
    small = (vec, vec, sds(jax.eval_shape(
        lambda: adamw.SlotHParams.broadcast(z))))
    scalar = _sds(one_chip, (), jnp.int32)
    hp = adamw.SlotHParams(**dict.fromkeys(
        adamw.SlotHParams._fields, _sds(one_chip, (), jnp.float32)))
    if write == "admit":
        key = _sds(one_chip, (2,), jnp.uint32)
        lowered = AS._admit_program.lower(
            cfg, tuple(shapes.items()), adamw.reset_slot, lora, opt, small,
            key, scalar, scalar, scalar, hp)
    else:
        one = jax.tree_util.tree_map(
            lambda x: _sds(one_chip, x.shape[:1] + x.shape[2:], x.dtype),
            lora)
        lowered = AS._restore_program.lower(lora, opt, small, scalar, one,
                                            one, one, scalar, scalar, hp)
    mem = lowered.compile().memory_analysis()
    state = sum(x.size * x.dtype.itemsize
                for x in jax.tree_util.tree_leaves((opt.mu, opt.nu)))
    assert mem.alias_size_in_bytes >= 0.99 * state
