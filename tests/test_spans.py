"""The program's host spans (``jax.profiler.TraceAnnotation``s named
``tune.*`` and ``serve.*``) as the profiler records them: a tiny tuning
task and a serving join under ``jax.profiler.trace``, read back from the
``.xplane.pb`` with ``ProfileData``."""
import pathlib

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs.base import TrainConfig
from repro.core import lora as LORA
from repro.core.early_exit import EarlyExitConfig
from repro.core.executor import BatchedExecutor
from repro.data.synthetic import make_task_dataset
from repro.models import model as M
from repro.serve import AdapterPool, ServingFrontend, ServingReplica
from tests.conftest import reduced_f32

TUNE_SPANS = {"tune.assemble", "tune.loss_fetch", "tune.observe",
              "tune.report", "tune.eval", "tune.eval_fetch", "tune.decide",
              "tune.best_ckpt", "tune.snapshot", "tune.admit",
              "tune.restore", "tune.evict"}


def host_spans(tdir: pathlib.Path, prefix: str):
    """[(name, {stat: value})] of the host events named ``prefix...`` in
    the one trace under ``tdir``, in start order."""
    from jax.profiler import ProfileData
    (path,) = tdir.rglob("*.xplane.pb")
    data = ProfileData.from_file(str(path))
    got = []
    for plane in data.planes:
        if plane.name.startswith("/host:"):
            for line in plane.lines:
                got += [(e.start_ns, e.name, dict(e.stats))
                        for e in line.events if e.name.startswith(prefix)]
    return [(name, stats) for _, name, stats in sorted(got)]


def plain_ints(spans) -> bool:
    return all(type(v) is int for _, stats in spans for v in stats.values())


def counts(spans):
    """{span name: the set of its stat names}, over ``spans``."""
    got = {}
    for name, stats in spans:
        got.setdefault(name, set()).update(stats)
    return got


@pytest.fixture(scope="module")
def cfg():
    return reduced_f32("paper-llama-tiny", num_layers=2, d_model=64,
                       vocab=128)


def test_executor_spans(cfg, tmp_path):
    """Two waves of two slots (widths 2 and 4 in lanes of 4), selection
    of two survivors that are restored, then their continued training:
    every executor span is recorded, and ``tune.assemble`` counts the real
    tokens the chunk reports give and Z x b_cap x S_cap positions; each
    train step's loss fetch counts Z fp32 losses to the host."""
    ds = make_task_dataset("t", cfg.vocab_size, seq_len=16, num_train=64,
                           num_val=8, difficulty=0.2)
    params = M.init_params(jax.random.PRNGKey(0), cfg)
    ex = BatchedExecutor(cfg, params, ds, Z=2, per_adapter_batch=4,
                         ee=EarlyExitConfig(warmup_ratio=0.5,
                                            select_ratio=0.5),
                         eval_every=2, seed=0)
    jobs = {f"j{i}": TrainConfig(learning_rate=1e-3, lora_rank=4,
                                 per_adapter_batch=2 + 2 * (i % 2),
                                 max_steps=4)
            for i in range(4)}
    with jax.profiler.trace(str(tmp_path)):
        reports = list(ex.run_task_chunks("t", jobs, total_steps=4))
    spans = host_spans(tmp_path, "tune.")
    assert {name for name, _ in spans} == TUNE_SPANS
    assemble = [stats for name, stats in spans if name == "tune.assemble"]
    assert sum(s["real_tokens"] for s in assemble) == \
        sum(r.tokens_executed for r in reports) > 0
    assert {s["positions"] for s in assemble} == {2 * 4 * 16}
    assert any(s["real_tokens"] < s["positions"] for s in assemble)
    fetches = [stats for name, stats in spans if name == "tune.loss_fetch"]
    assert len(fetches) == len(assemble)
    assert fetches == [{"d2h_bytes": 2 * 4}] * len(fetches)
    copies = {k: v for k, v in counts(spans).items() if v}
    assert copies == {"tune.assemble": {"real_tokens", "positions"},
                      **{n: {"d2h_bytes"} for n in (
                          "tune.loss_fetch", "tune.eval_fetch",
                          "tune.best_ckpt", "tune.snapshot")}}
    assert plain_ints(spans)


def two_adapter_frontend(cfg, **replica) -> ServingFrontend:
    """A frontend over two published adapters (ranks 4 and 8), 2 lanes
    each, with two 5-token prompts queued, one an adapter."""
    key = jax.random.PRNGKey(0)
    params = M.init_params(key, cfg)
    stack = LORA.init_lora_tree(key, cfg, 2, jnp.asarray([4, 8]),
                                M.target_shapes(cfg))
    pool = AdapterPool(cfg, 2)
    for z, rank in enumerate([4, 8]):
        pool.publish(f"a{z}", jax.tree_util.tree_map(
            lambda x: np.asarray(x[:, z]), stack), rank)
    front = ServingFrontend(ServingReplica(cfg, params, pool, lanes=2,
                                           max_len=32, **replica))
    rng = np.random.default_rng(0)
    for z in range(2):
        front.submit(f"a{z}", rng.integers(0, cfg.vocab_size, 5), 4)
    return front


def test_serving_spans(cfg, tmp_path):
    """One ``step_continuous`` that joins two requests (the fused
    join-and-decode launch), then one decode step."""
    front = two_adapter_frontend(cfg)
    with jax.profiler.trace(str(tmp_path)):
        front.step_continuous()
        front.step_continuous()
    spans = host_spans(tmp_path, "serve.")
    names = [name for name, _ in spans]
    for name in ("serve.fill", "serve.join", "serve.request",
                 "serve.dispatch", "serve.token_fetch", "serve.emit"):
        assert name in names
    assert {k: v for k, v in counts(spans).items() if v} == {
        "serve.request": {"queue_ms", "join_wait_ms"},
        "serve.dispatch": {"active_lanes", "lanes"}}
    assert names.count("serve.request") == 2
    dispatch = [s for name, s in spans if name == "serve.dispatch"]
    assert dispatch == [{"active_lanes": 2, "lanes": 4}] * 2
    assert plain_ints(spans)


def test_stream_joiners_get_request_spans(cfg, tmp_path):
    """A ring-cache replica streams each joiner's prompt through decode
    after a lane reset: that reset is the joiner's launch, and each joiner
    still gets its one ``serve.request`` event."""
    front = two_adapter_frontend(cfg, ring=True)
    assert front.replica.ring
    with jax.profiler.trace(str(tmp_path)):
        front.step_continuous()
    spans = host_spans(tmp_path, "serve.")
    requests = [stats for name, stats in spans if name == "serve.request"]
    assert len(requests) == 2
    assert all(set(r) == {"queue_ms", "join_wait_ms"} for r in requests)
    assert plain_ints(spans)
