#!/usr/bin/env python3
"""Smoke run of the tune-to-serve path on a TPU at stablelm-3b's published
widths (32 layers, d_model 2560, 32 heads of 80, d_ff 6912, vocab 50304,
bf16), with random weights made from ``--seed``.

    python chip_smoke.py                  # one chip: device, tune, serve
    python chip_smoke.py --four-chips     # four chips: adapter-parallel step
    JAX_PLATFORMS=cpu python chip_smoke.py --rehearse [--four-chips]

Phases, all in this one process (a child would find the chip taken):

* device  -- platform, kind and count; anything but a TPU fails.
* tune    -- a ``TuningService`` task (ranks {8, 16, 64} x lr {1e-4, 1e-3},
  batch 4, seq 512, ``num_slots=0`` so the memory model picks Z, early exit
  on) runs to idle with the Pallas kernels compiled by Mosaic; the mixed
  ranks take the rank-local grouped-LoRA kernels. Before it, the first two
  fused steps of the task's first slot mix run once on the kernels and once
  on the ``jnp`` reference, from adapters whose B is random too (so their
  output counts from the first step); per-slot losses and gradient norms
  must agree, and so must the adapters' own share of the first loss.
* serve   -- the winner and two adapters of other ranks are published into
  an ``AdapterPool``; a ``ServingFrontend`` serves 8 greedy requests (prompt
  128, 32 new tokens) in continuous mode. The kernels' lane-prefill logits
  must agree with the ``jnp`` path's.
* --four-chips -- only the multi-chip path: the adapter-parallel train step
  (``launch/train.py``) on a (data=4, model=1) mesh at Z=4, from the same
  random-B adapters; each slot's first-step loss and gradient norm, and its
  second-step loss (as a share of the step's move), must match the same
  adapter trained alone (Z=1) on one chip.

Every failed phase exits non-zero. The last line of standard output is
``{"ok": true, "device": {"platform": ..., "kind": ..., "count": ...}}``.

``--rehearse`` runs the same phases on a tiny variant of the model with the
interpreted kernels, on whatever device JAX finds (the CPU rehearsal).

Compile cache: ``$JAX_COMPILATION_CACHE_DIR`` when it is set, else
``<repo>/.jax_cache``.
"""
from __future__ import annotations

import argparse
import dataclasses
import gc
import json
import os
import sys
import time

REPO = os.path.dirname(os.path.abspath(__file__))
sys.path.insert(0, os.path.join(REPO, "src"))

RANKS = (8, 16, 64)
LRS = (1e-4, 1e-3)
# bf16 carries 8 significant bits (unit roundoff 2**-8 = 3.9e-3); the kernel
# and jnp paths round and reduce in different orders through 32 layers. On a
# TPU v5e the kernel-vs-jnp gaps were 5.7e-5 (loss) and 6.3e-4 (gradient
# norm) relative, the four-chip-vs-one-chip loss gap 3.1e-5.
LOSS_RTOL = 1e-3        # per-slot loss
GRAD_RTOL = 1e-2        # per-slot gradient norm
LOGIT_RTOL = 5e-2       # relative L2 error of the prefill logits
# the seeded adapters' B has std B_SCALE / sqrt(d_model) (the trainer starts
# at B = 0), so their output is the same share of a layer's at any width
B_SCALE = 0.5
# The kernel-vs-jnp step-1 loss gap, as a share of what the adapters add to
# the loss (jnp's loss minus the backbone alone): 4.9% and 2.5% on a v5e. A
# kernel path whose adapters added nothing would be off by 100%.
ADAPTER_RTOL = 0.2
# Adam's first update moves each element by about lr * sign(g), so entries
# whose gradient bf16 rounding leaves near zero can take the opposite step:
# after an update, two correct runs differ by a share of the step's own
# loss move, not of the loss (four-chip vs one-chip on a v5e at lr 1e-3:
# 0.16% and 0.94% of a 1.4-1.5 move). An update that went wrong or landed
# on another slot's adapter misses by about the whole move.
UPDATE_RTOL = 0.05


@dataclasses.dataclass(frozen=True)
class Size:
    batch: int = 4          # per-adapter batch
    seq: int = 512
    steps: int = 6          # per-job step budget
    prompt: int = 128
    new_tokens: int = 32
    requests: int = 8
    lanes: int = 4


FULL = Size()
REHEARSAL = Size(seq=32, steps=4, prompt=16, new_tokens=8)


class SmokeFailure(Exception):
    """A phase's output is wrong."""


def check(ok: bool, what: str) -> None:
    if not ok:
        raise SmokeFailure(what)


def log(phase: str, msg: str) -> None:
    print(f"[{phase}] {msg}", flush=True)


def rel_err(a, b) -> float:
    import numpy as np
    a, b = np.asarray(a, np.float64), np.asarray(b, np.float64)
    return float(np.linalg.norm(a - b) / max(np.linalg.norm(b), 1e-30))


def use_compile_cache() -> None:
    """JAX reads ``JAX_COMPILATION_CACHE_DIR`` itself; without it the cache
    goes to a fixed path in the repo, so later runs find it again."""
    import jax
    if not os.environ.get("JAX_COMPILATION_CACHE_DIR"):
        jax.config.update("jax_compilation_cache_dir",
                          os.path.join(REPO, ".jax_cache"))


def model_config(rehearse: bool):
    from repro.configs.registry import get_arch
    cfg = get_arch("stablelm-3b")
    if rehearse:
        cfg = cfg.reduced(num_layers=2, d_model=128, vocab=256)
        cfg = dataclasses.replace(cfg, lora=dataclasses.replace(
            cfg.lora, r_max=max(RANKS)))        # keep the ranks mixed
    return cfg


def peak_bytes(device):
    stats = device.memory_stats() or {}
    return stats.get("peak_bytes_in_use"), stats.get("bytes_limit")


def gib(x) -> str:
    return "n/a" if x is None else f"{x / 2 ** 30:.3f} GiB"


# ---------------------------------------------------------------------------
# device
# ---------------------------------------------------------------------------

def device_phase(rehearse: bool, chips: int) -> dict:
    import jax
    devs = jax.devices()
    dev = {"platform": devs[0].platform, "kind": devs[0].device_kind,
           "count": len(devs)}
    log("device", json.dumps(dev))
    log("device", f"compile cache {jax.config.jax_compilation_cache_dir}")
    check(rehearse or dev["platform"] == "tpu",
          f"no TPU: JAX found {dev['platform']}")
    check(dev["count"] >= chips, f"need {chips} devices, have {dev['count']}")
    return dev


# ---------------------------------------------------------------------------
# tune
# ---------------------------------------------------------------------------

def smoke_task(cfg, size: Size, seed: int):
    from repro.core.engine import Task
    from repro.data.synthetic import make_task_dataset
    ds = make_task_dataset("smoke", cfg.vocab_size, seq_len=size.seq,
                           num_train=64, seed=seed)
    return Task(model=cfg, dataset=ds, name="smoke", seed=seed,
                search_space={"rank": list(RANKS), "lr": list(LRS),
                              "batch_size": [size.batch]},
                num_slots=0, max_steps=size.steps)


def seeded_lora(cfg, ranks, seed: int) -> dict:
    """A [L, Z, ...] slot tree initialised as the trainer does, but with B
    random too (masked to each slot's rank), so the adapters' output
    counts from the first step."""
    import jax
    import jax.numpy as jnp
    from repro.core import lora as LORA
    from repro.models import model as M
    ranks = jnp.asarray(ranks, jnp.int32)
    kA, kB = jax.random.split(jax.random.PRNGKey(seed))
    tree = LORA.init_lora_tree(kA, cfg, len(ranks), ranks,
                               M.target_shapes(cfg))
    std = B_SCALE / cfg.d_model ** 0.5
    mask = LORA.rank_mask(ranks, cfg.lora.r_max)[None, :, :, None]
    keys = dict(zip(tree, jax.random.split(kB, len(tree))))
    return {t: {"A": ab["A"], "B": std * mask * jax.random.normal(
                keys[t], ab["B"].shape)}
            for t, ab in tree.items()}


def train_parity(cfg, params, task, Z: int, kernels: str, seed: int) -> None:
    """The first two fused steps of the task's first Z jobs, once on the
    kernels and once on jnp, from the same seeded adapters, batches and
    params; then one jnp step from B = 0, the backbone alone."""
    import jax
    import jax.numpy as jnp
    import numpy as np
    from repro.core import lora as LORA
    from repro.core import steps as STEPS
    from repro.data.synthetic import SlotBatcher
    from repro.models import backend as MB
    from repro.models import model as M
    from repro.optim import adamw

    jobs = list(task.jobs().values())[:Z]
    ranks = jnp.asarray([min(tc.lora_rank, cfg.lora.r_max) for tc in jobs],
                        jnp.int32)
    hp = adamw.SlotHParams.broadcast(Z)
    for z, tc in enumerate(jobs):
        hp = hp.replace_slot(z, lr=tc.learning_rate, wd=tc.weight_decay,
                             beta1=tc.beta1, beta2=tc.beta2,
                             grad_clip=tc.grad_clip)
    active = jnp.ones((Z,), jnp.int32)
    batcher = SlotBatcher(task.resolved_dataset(), Z,
                          jobs[0].per_adapter_batch, seed=seed)
    batches = []
    for _ in range(2):
        t, l = batcher.next_batch()
        batches.append({"tokens": jnp.asarray(t), "labels": jnp.asarray(l),
                        "slot_ranks": ranks})
    seeded = lambda: seeded_lora(cfg, ranks, seed)      # noqa: E731
    lora_shape = jax.eval_shape(seeded)
    opt_shape = jax.eval_shape(lambda l: adamw.init_state(l, Z), lora_shape)

    def compiled(backend: str):
        t0 = time.perf_counter()
        with LORA.backend(backend), MB.backend(backend):
            step = STEPS.jit_train_step(cfg).lower(
                params, lora_shape, opt_shape, hp, active, ranks,
                batches[0]).compile()
        msg = f"{backend}: compile {time.perf_counter() - t0:.2f} s"
        if backend != "jnp":
            msg += f", {step.as_text().count('tpu_custom_call')} " \
                   "tpu_custom_calls"
        log("tune", msg)
        return step

    def trajectory(step, lora, n: int):
        opt = adamw.init_state(lora, Z)
        out = []
        for batch in batches[:n]:
            t0 = time.perf_counter()
            lora, opt, m = step(params, lora, opt, hp, active, ranks, batch)
            out.append((np.asarray(m["per_slot_loss"]),
                        np.asarray(m["grad_norm"]),
                        time.perf_counter() - t0))
        return out

    def steps_s(out) -> str:
        return ", ".join(f"{s:.3f} s" for _, _, s in out)

    got = trajectory(compiled(kernels), seeded(), 2)
    log("tune", f"{kernels} steps {steps_s(got)}")
    step = compiled("jnp")
    ref = trajectory(step, seeded(), 2)
    log("tune", f"jnp steps {steps_s(ref)}")
    zero_b = LORA.init_lora_tree(jax.random.PRNGKey(seed), cfg, Z, ranks,
                                 M.target_shapes(cfg))
    bare = trajectory(step, zero_b, 1)[0][0]
    del step
    for i, ((lk, gk, _), (lj, gj, _)) in enumerate(zip(got, ref)):
        log("tune", f"step {i + 1} per-slot loss {kernels} {lk.tolist()} "
            f"jnp {lj.tolist()}; grad norm {kernels} {gk.tolist()} "
            f"jnp {gj.tolist()}")
        check(np.all(np.isfinite(lk)) and np.all(np.isfinite(gk)),
              f"step {i + 1}: non-finite loss or gradient on the kernels")
        check(np.all(np.abs(lk - lj) <= LOSS_RTOL * np.abs(lj)),
              f"step {i + 1}: kernel losses {lk} vs jnp {lj} "
              f"(rtol {LOSS_RTOL})")
        check(np.all(np.abs(gk - gj) <= GRAD_RTOL * np.abs(gj)),
              f"step {i + 1}: kernel grad norms {gk} vs jnp {gj} "
              f"(rtol {GRAD_RTOL})")
    effect = np.abs(ref[0][0] - bare)
    share = np.abs(got[0][0] - ref[0][0]) / np.maximum(effect, 1e-30)
    log("tune", f"step 1 backbone-alone (B = 0) loss {bare.tolist()}; the "
        f"seeded adapters move it by {effect.tolist()}; the kernel-vs-jnp "
        f"gap is {share.tolist()} of that (limit {ADAPTER_RTOL})")
    check(np.all(share <= ADAPTER_RTOL),
          f"kernel adapters' share of the loss off by {share} of it")


def tune_phase(cfg, size: Size, seed: int, kernels: str):
    """Returns (engine, task, TaskResult)."""
    import jax
    import numpy as np
    from repro.core import lora as LORA
    from repro.core.engine import EarlyExit, Engine
    from repro.core.service import TuningService
    from repro.models import backend as MB
    from repro.sched import profiler

    task = smoke_task(cfg, size, seed)
    engine = Engine(total_gpus=1, eval_every=max(size.steps // 2, 1))
    Z = engine.pick_slots(task)
    predicted = profiler.analytic_peak_memory(cfg, Z, size.batch, size.seq)
    log("tune", f"{len(task.jobs())} jobs, memory model picks Z={Z} "
        f"(predicted peak {gib(predicted)})")
    params = engine.base_params(cfg, seed)

    train_parity(cfg, params, task, Z, kernels, seed)
    gc.collect()

    svc = TuningService(engine=engine)
    t0 = time.perf_counter()
    with LORA.backend(kernels), MB.backend(kernels):
        handle = svc.submit(task, early_exit=EarlyExit(warmup_ratio=0.5))
        report = svc.run_until_idle()
    wall = time.perf_counter() - t0
    result = handle.result()
    steps = sum(r.steps_trained for r in result.job_results.values())
    step_s = engine.profile_store.wall_step_time(engine.profile_key(task))
    log("tune", f"service ran to idle in {wall:.2f} s wall "
        f"(compiles included); {steps} job-steps; observed step wall "
        f"{step_s if step_s is None else f'{step_s:.3f}'} s; "
        f"exits {result.exit_counts}")
    peak, limit = peak_bytes(jax.devices()[0])
    log("tune", f"peak_bytes_in_use {gib(peak)} of bytes_limit {gib(limit)}; "
        f"memory model predicted {gib(predicted)} for Z={Z}")
    check(not report.cancelled, f"cancelled: {report.cancelled}")
    check(result.best_job is not None and np.isfinite(result.best_val),
          "no finite winner")
    best = result.job_results[result.best_job]
    check(best.adapter is not None, "winner has no adapter checkpoint")
    log("tune", f"winner {result.best_job} (rank {best.config.lora_rank}, "
        f"lr {best.config.learning_rate}) val loss {result.best_val:.4f}")
    return engine, task, result


# ---------------------------------------------------------------------------
# serve
# ---------------------------------------------------------------------------

def seeded_adapter(cfg, rank: int, seed: int) -> dict:
    """A single adapter ([L, ...] tree) with A and B both random."""
    return {t: {k: v[:, 0] for k, v in ab.items()}
            for t, ab in seeded_lora(cfg, [rank], seed).items()}


def prefill_parity(cfg, params, pool, prompts, max_len: int,
                   kernels: str) -> None:
    """Lane-prefill logits of every pool slot on the kernels vs jnp."""
    import jax
    import jax.numpy as jnp
    import numpy as np
    from repro.core import lora as LORA
    from repro.core import steps as STEPS
    from repro.models import backend as MB
    from repro.models import model as M

    Z, lanes = pool.Z, prompts.shape[1]
    mask = jnp.ones((Z, lanes), bool)
    plens = jnp.full((Z, lanes), prompts.shape[2], jnp.int32)

    def logits(backend: str):
        prefill = STEPS.make_lane_prefill_step(cfg)

        def ranked(params, lora, cache, tokens, mask, plens, ranks):
            with LORA.slot_ranks(ranks):
                return prefill(params, lora, cache, tokens, mask, plens)[0]

        cache = M.init_cache(cfg, Z, lanes, max_len, per_lane=True)
        with LORA.backend(backend), MB.backend(backend):
            out = jax.jit(ranked)(params, pool.lora, cache,
                                  jnp.asarray(prompts), mask, plens,
                                  pool.ranks)
        return np.asarray(out.astype(jnp.float32))

    got, ref = logits(kernels), logits("jnp")
    err = rel_err(got, ref)
    agree = float(np.mean(got.argmax(-1) == ref.argmax(-1)))
    log("serve", f"prefill logits {kernels} vs jnp: relative L2 error "
        f"{err:.3e} (limit {LOGIT_RTOL}), greedy-token agreement "
        f"{agree:.3f}")
    check(np.all(np.isfinite(got)), "non-finite prefill logits")
    check(err <= LOGIT_RTOL, f"prefill logits differ: {err:.3e}")


def serve_phase(cfg, params, task, result, size: Size, seed: int,
                kernels: str) -> None:
    import numpy as np
    from repro.core import lora as LORA
    from repro.models import backend as MB
    from repro.serve.frontend import ServingFrontend
    from repro.serve.pool import AdapterPool
    from repro.serve.replica import ServingReplica

    best = result.job_results[result.best_job]
    win_rank = best.config.lora_rank
    others = [r for r in RANKS if r != win_rank][:2]
    max_len = size.prompt + size.new_tokens
    pool = AdapterPool(cfg, Z=1 + len(others))
    replica = ServingReplica(cfg, params, pool, lanes=size.lanes,
                             max_len=max_len)
    front = ServingFrontend(replica, mode="continuous")
    front.publish("winner", best.adapter, win_rank)
    for i, r in enumerate(others):
        front.publish(f"rank{r}", seeded_adapter(cfg, r, seed + 1 + i), r)
    log("serve", f"pool: {pool.resident()} ranks {pool.slot_rank}")

    rows = task.resolved_dataset().train
    rng = np.random.default_rng(seed)
    pick = rng.choice(len(rows), size=size.requests, replace=False)
    prompts = rows[pick, :size.prompt].astype(np.int32)
    ids = list(pool.resident())

    lanes = np.zeros((pool.Z, size.lanes, size.prompt), np.int32)
    for i in range(pool.Z * size.lanes):
        lanes[i // size.lanes, i % size.lanes] = prompts[i % len(prompts)]
    prefill_parity(cfg, params, pool, lanes, max_len, kernels)

    t0 = time.perf_counter()
    with LORA.backend(kernels), MB.backend(kernels):
        rids = [front.submit(ids[i % len(ids)], prompts[i], size.new_tokens)
                for i in range(size.requests)]
        out = front.drain()
    wall = time.perf_counter() - t0
    served = sum(len(out.get(r, ())) for r in rids)
    log("serve", f"{len(out)} requests, {served} tokens in {wall:.2f} s "
        f"wall (compiles included), {replica.total_decode_steps} decode "
        f"steps, {replica.block_prefills} prefill launches")
    for r in rids:
        check(len(out.get(r, ())) == size.new_tokens,
              f"request {r} got {len(out.get(r, ()))} tokens")
        check(all(0 <= t < cfg.vocab_size for t in out[r]),
              f"request {r}: token out of vocabulary")


# ---------------------------------------------------------------------------
# four chips
# ---------------------------------------------------------------------------

def four_chip_phase(cfg, size: Size, seed: int) -> None:
    """Adapter-parallel steps on (data=4, model=1) at Z=4 from seeded
    random-B adapters; each slot's losses against the same adapter
    trained alone on one chip."""
    import jax
    import numpy as np
    from repro.data.synthetic import SlotBatcher
    from repro.launch.mesh import make_mesh
    from repro.launch.train import AdapterParallelRun

    devs = jax.devices()
    ranks = [RANKS[z % len(RANKS)] for z in range(4)]
    task = smoke_task(cfg, size, seed)
    tokens, labels = SlotBatcher(task.resolved_dataset(), 4, size.batch,
                                 seed=seed).next_batch()
    init = jax.tree_util.tree_map(np.asarray, seeded_lora(cfg, ranks, seed))

    def trajectory(run, tokens, labels):
        """[(per-slot loss, per-slot grad norm, seconds)] over two steps."""
        out = []
        for _ in range(2):
            t1 = time.perf_counter()
            m = run.step(tokens, labels)
            out.append((np.asarray(m["per_slot_loss"]),
                        np.asarray(m["grad_norm"]),
                        time.perf_counter() - t1))
        return out

    mesh = make_mesh((4, 1), ("data", "model"), devs[:4])
    t0 = time.perf_counter()
    run = AdapterParallelRun(cfg, mesh, ranks, seed=seed, lora=init)
    got = trajectory(run, tokens, labels)
    for loss, norm, s in got:
        log("four-chips", f"Z=4 step {s:.3f} s per-slot loss "
            f"{loss.tolist()} grad norm {norm.tolist()}")
    log("four-chips", f"Z=4 run {time.perf_counter() - t0:.2f} s wall "
        "(compiles included)")
    for d in devs[:4]:
        peak, limit = peak_bytes(d)
        log("four-chips", f"device {d.id}: peak_bytes_in_use {gib(peak)} "
            f"of {gib(limit)}")
    del run
    gc.collect()

    one = make_mesh((1, 1), ("data", "model"), devs[:1])
    for z in range(4):
        slot = jax.tree_util.tree_map(lambda x: x[:, z:z + 1], init)
        ref = AdapterParallelRun(cfg, one, [ranks[z]], seed=seed, lora=slot)
        alone = trajectory(ref, tokens[z:z + 1], labels[z:z + 1])
        del ref
        gc.collect()
        (l1, g1), (l2, _) = [(float(l[z]), float(g[z])) for l, g, _ in got]
        (a1, h1), (a2, _) = [(float(l[0]), float(g[0])) for l, g, _ in alone]
        share = abs(l2 - a2) / max(abs(a1 - a2), 1e-30)
        log("four-chips", f"slot {z} (rank {ranks[z]}): Z=4 losses "
            f"[{l1}, {l2}], step-1 grad norm {g1}; alone on one chip "
            f"[{a1}, {a2}], {h1}; step-2 gap {share:.3e} of the step's "
            f"move (limit {UPDATE_RTOL})")
        check(all(np.isfinite([l1, l2, g1])), f"slot {z}: non-finite output")
        check(abs(l1 - a1) <= LOSS_RTOL * abs(a1),
              f"slot {z} step 1: loss {l1} vs {a1} (rtol {LOSS_RTOL})")
        check(abs(g1 - h1) <= GRAD_RTOL * abs(h1),
              f"slot {z} step 1: grad norm {g1} vs {h1} (rtol {GRAD_RTOL})")
        check(share <= UPDATE_RTOL,
              f"slot {z} step 2: loss {l2} vs {a2}, {share:.3e} of the "
              f"step's move {a1 - a2}")


# ---------------------------------------------------------------------------

def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--four-chips", action="store_true",
                    help="run only the four-chip adapter-parallel phase")
    ap.add_argument("--rehearse", action="store_true",
                    help="tiny model, interpreted kernels, any device")
    args = ap.parse_args(argv)

    use_compile_cache()
    phase = "device"
    try:
        dev = device_phase(args.rehearse, 4 if args.four_chips else 1)
        cfg = model_config(args.rehearse)
        size = REHEARSAL if args.rehearse else FULL
        kernels = "pallas_interpret" if args.rehearse else "pallas"
        log("device", f"model {cfg.name}: {cfg.num_layers} layers, d_model "
            f"{cfg.d_model}, {cfg.num_heads} heads of {cfg.resolved_head_dim}"
            f", d_ff {cfg.d_ff}, vocab {cfg.vocab_size}, {cfg.dtype}; "
            "kernels " + ("jnp (GSPMD)" if args.four_chips else kernels))
        if args.four_chips:
            phase = "four-chips"
            four_chip_phase(cfg, size, args.seed)
        else:
            phase = "tune"
            engine, task, result = tune_phase(cfg, size, args.seed, kernels)
            gc.collect()        # the service's slot state leaves the chip
            phase = "serve"
            serve_phase(cfg, engine.base_params(cfg, args.seed), task,
                        result, size, args.seed, kernels)
    except Exception as e:      # report the phase, then fail the run
        import traceback
        traceback.print_exc()
        print(f"[{phase}] FAILED: {type(e).__name__}: {e}", flush=True)
        return 1
    print(json.dumps({"ok": True, "device": dev}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
