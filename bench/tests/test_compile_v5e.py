"""Each cell's programs compile for a described TPU v5e at the cell's own
sizes, and fit its memory: the tuning cells' donated train step at the Z
the engine's memory model picks (with the batch variants the mix makes),
and the serving cell's fused join-and-decode (at the longest prompt
bucket) and decode-only steps over all its lanes. Nothing runs.

The topology is described inside a fixture: only one process may hold
libtpu, and every pytest-xdist worker imports this file."""
import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from bench import harness, workload
from bench.drivers.serve import buckets

HBM = 15.75 * 2 ** 30      # what the v5e compiler lets one program hold


@pytest.fixture(scope="module")
def one_chip():
    from jax.experimental import topologies
    try:
        topo = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    return SingleDeviceSharding(topo.devices[0])


@pytest.fixture(autouse=True)
def no_cache():
    jax.config.update("jax_enable_compilation_cache", False)
    yield


def cell(name):
    bench = harness.load_json(harness.ROOT, "BENCHMARK.json")
    return harness.Context(bench, name, 0, 1.0, False)


def sds(one_chip, tree):
    return jax.tree_util.tree_map(
        lambda x: jax.ShapeDtypeStruct(x.shape, x.dtype, sharding=one_chip),
        tree)


def peak(compiled) -> int:
    m = compiled.memory_analysis()
    print(m)
    return (m.argument_size_in_bytes + m.output_size_in_bytes
            + m.temp_size_in_bytes - m.alias_size_in_bytes)


@pytest.mark.parametrize("name,Z", [("stablelm-3b.tune.mixed-rank", 2),
                                    ("granite-8b-l12.tune.mixed-width", 3)])
def test_tuning_step_fits_at_picked_slots(one_chip, name, Z):
    from repro.core import lora as LORA
    from repro.core import steps as STEPS
    from repro.core.engine import Engine, Task
    from repro.data.synthetic import TaskDataset
    from repro.models import model as M
    from repro.optim import adamw
    ctx = cell(name)
    cfg, tr = ctx.cfg, ctx.traffic
    rows = workload.tune_rows(dict(tr, num_train=8, num_val=4), 0)
    task = Task(model=cfg, num_slots=0, search_space=tr["search_space"],
                dataset=TaskDataset("compile", rows["train"], rows["val"],
                                    cfg.vocab_size, 0))
    assert Engine(total_gpus=1).pick_slots(task) == Z
    b, S = max(tr["search_space"]["batch_size"]), tr["seq_len"]

    def adapters():
        return LORA.init_lora_tree(jax.random.PRNGKey(0), cfg, Z,
                                   jnp.zeros((Z,), jnp.int32),
                                   M.target_shapes(cfg))

    params = sds(one_chip, jax.eval_shape(
        lambda: M.init_params(jax.random.PRNGKey(0), cfg)))
    lora = sds(one_chip, jax.eval_shape(adapters))
    opt = sds(one_chip, jax.eval_shape(
        lambda: adamw.init_state(adapters(), Z)))
    hp = sds(one_chip, jax.eval_shape(lambda: adamw.SlotHParams.broadcast(Z)))
    vec = jax.ShapeDtypeStruct((Z,), jnp.int32, sharding=one_chip)
    tok = jax.ShapeDtypeStruct((Z, b, S), jnp.int32, sharding=one_chip)
    batch = {"tokens": tok, "labels": tok, "slot_ranks": vec}
    if len(tr["search_space"]["batch_size"]) > 1:
        batch["slot_rows"] = vec
    compiled = STEPS.jit_train_step(cfg).lower(
        params, lora, opt, hp, vec, vec, batch).compile()
    assert peak(compiled) < HBM


def test_serving_steps_fit_at_the_lane_count(one_chip):
    from repro.core import lora as LORA
    from repro.core.steps import make_join_decode_step, make_serve_step
    from repro.models import model as M
    ctx = cell("stablelm-3b.serve.chat-poisson")
    cfg, tr = ctx.cfg, ctx.traffic
    Z, lanes, L = len(tr["adapter_ranks"]), tr["lanes"], tr["max_len"]
    P = buckets(tr)[-1]
    join, serve = make_join_decode_step(cfg), make_serve_step(cfg)

    def ranked_join(params, lora, cache, tokens, mask, plens, cur, active,
                    ranks):
        with LORA.slot_ranks(ranks):
            return join(params, lora, cache, tokens, mask, plens, cur,
                        active)

    def ranked_decode(params, lora, cache, tokens, active, ranks):
        with LORA.slot_ranks(ranks):
            return serve(params, lora, cache, tokens, active)

    params = sds(one_chip, jax.eval_shape(
        lambda: M.init_params(jax.random.PRNGKey(0), cfg)))
    lora = sds(one_chip, jax.eval_shape(lambda: LORA.init_lora_tree(
        jax.random.PRNGKey(0), cfg, Z, jnp.zeros((Z,), jnp.int32),
        M.target_shapes(cfg))))
    cache = sds(one_chip, jax.eval_shape(
        lambda: M.init_cache(cfg, Z, lanes, L, per_lane=True)))
    grid = jax.ShapeDtypeStruct((Z, lanes), jnp.int32, sharding=one_chip)
    mask = jax.ShapeDtypeStruct((Z, lanes), jnp.bool_, sharding=one_chip)
    ranks = jax.ShapeDtypeStruct((Z,), jnp.int32, sharding=one_chip)
    toks = jax.ShapeDtypeStruct((Z, lanes, P), jnp.int32, sharding=one_chip)
    j = jax.jit(ranked_join).lower(params, lora, cache, toks, mask, grid,
                                   grid, mask, ranks).compile()
    d = jax.jit(ranked_decode).lower(params, lora, cache, grid, mask,
                                     ranks).compile()
    assert peak(j) < HBM and peak(d) < HBM
