"""Rank-local grouped GEMM vs rank-masked execution on a RANK-SWEEP mix.

Rank is the single most-tuned LoRA hyperparameter, so a tuning workload
naturally sweeps r = 4..64 — but the zero-masked (§A.1 padded) execution
bills every slot at r_max: a rank-4 adapter co-located with a rank-64 one
pays 16x its true FLOPs in all six grouped GEMMs, and the §A.3 memory
model budgets replicas as if every slot were r_max wide. The rank-local
path masks each slot to its true rank inside the kernels and makes the
§A.3 budget rank-aware (rank-weighted FLOP-tokens at TRUE ranks). This
bench quantifies both effects:

1. **Cluster A/B/C (virtual time).** One long fusable host, exclusive hog
   tasks pinning the remaining GPUs, and a stream of small fusable tasks
   sweeping ranks {4, 8, 16, 32, 64}, run three ways: ``exclusive`` (no
   fusion), ``rankmasked`` (fusion with every task CHARGED r_max by the
   memory model and STEPPED at r_max cost — the padded execution), and
   ``ranklocal`` (true-rank §A.3 charges + true-rank step times). Task
   results must be identical in all three; rank-local must beat
   rank-masked on makespan AND effective utilization.

2. **Isolation check (real training).** Tasks with DIFFERENT true ranks
   fused on one real ``SharedBackboneExecutor`` vs each alone: loss
   histories bitwise identical, best-vals equal.

3. **Kernel check.** Concrete full-rank rank-local calls bitwise-equal
   the dense kernels; wall-time of the interpret-mode fwd+VJP on a
   mixed-rank stack is reported for observability (interpret mode runs
   the grid as a host loop, so treat it as a smoke signal, not a TPU
   projection), alongside the adapter-GEMM FLOP ratio from the roofline
   accounting (the MXU work the dead-tile skip reclaims).

Emits BENCH_ranklocal.json. ``--smoke`` shrinks the mix (CI artifact).
"""
from __future__ import annotations

import argparse
import dataclasses
import json
import sys
import time

import jax
import jax.numpy as jnp
import numpy as np

from repro.configs.base import TrainConfig
from repro.configs.registry import get_arch
from repro.core.early_exit import EarlyExitConfig
from repro.core.executor import (SharedBackboneExecutor, TaskLifecycle,
                                 run_colocated)
from repro.data.synthetic import SlotBatcher, make_task_dataset
from repro.kernels.grouped_lora import ops as kops
from repro.models import model as M
from repro.roofline.analysis import ranklocal_savings
from repro.sched import profiler
from repro.sched.cluster import (ElasticClusterRuntime, SimulatedTaskDriver,
                                 execute_static, sim_colo_spec,
                                 sim_task_spec)
from repro.sched.events import EventKind
from repro.sched.inter_task import solve
from repro.sched.intra_task import MemoryModel

FUSE_ARCH = "stablelm-3b"          # the shared-backbone family (1 GPU)
HOG_MIX = [("glm4-9b", 2), ("granite-8b", 1)]
SEQ = 1024
R_MAX = 64
RANK_SWEEP = (4, 8, 16, 32, 64)    # the rank-sweep payload, cycling
HOST_RANK = 16
RELAXED_KEY = (FUSE_ARCH, 1, "sft")

# replica memory model: token term + rank-weighted FLOP-token term (k2 =
# one token-equivalent per 8 rank units, so a rank-8 slot doubles its
# token charge and a rank-64 slot pays 9x). Rank-masked mode charges
# every request r_max=64 — the padded §A.3 accounting this PR replaces —
# under which the host replica can carry at most ONE guest at a time,
# while true-rank charges fit the whole rank sweep concurrently.
MEM = MemoryModel(k0=0.0, k1=1.0, seq_len=SEQ, capacity=150_000,
                  safety_margin=0.9, k2=1.0 / 8, r_max=R_MAX)


def step_time(cfg, Z: int, b: int, rank: int, gpus: int) -> float:
    """Fused-step seconds with every slot at ``rank`` (the §A.3 rank-aware
    cost model; rank-masked execution bills r_max)."""
    return profiler.fused_step_time(cfg, [b * SEQ] * Z, [rank] * Z, gpus)


def build_workload(num_small: int, seed: int = 0):
    """(spec, factory, colo, true_rank) tuples with RELAXED width-free
    keys; ``run_cluster`` rewrites rank charges + step times per mode."""
    rng = np.random.default_rng(seed)
    cfg = get_arch(FUSE_ARCH)
    tasks = []

    def sim(name, *, K, Z, total, warm, st, gpus, colo, rank):
        spec = sim_task_spec(name, K=K, Z=Z, total_steps=total,
                             warmup_steps=warm, step_time_s=st, gpus=gpus)

        def factory(name=name, K=K, Z=Z, total=total, warm=warm, st=st):
            return SimulatedTaskDriver(name, K=K, Z=Z, total_steps=total,
                                       warmup_steps=warm, step_time_s=st)
        return (spec, factory, colo, rank)

    # host: Z=8 slots at rank 16; Pattern-3 keeps top 2 of 8
    st_host = step_time(cfg, 8, 4, HOST_RANK, 1)
    host_total = int(rng.integers(1100, 1400))
    host = sim("host", K=8, Z=8, total=host_total, warm=host_total // 20,
               st=st_host, gpus=1, rank=HOST_RANK,
               colo=sim_colo_spec(RELAXED_KEY, K=8, Z=8,
                                  per_adapter_batch=4, seq_len=SEQ,
                                  replica_slots=16, mem=MEM,
                                  lora_rank=HOST_RANK))
    tasks.append(host)
    host_dur = host[0].duration
    # hogs: other archs, exclusive, pin the remaining GPUs
    for arch, gpus in HOG_MIX:
        hcfg = get_arch(arch)
        st = profiler.profile_task(hcfg, 4, 4, SEQ, gpus).step_time_s
        warm = 50
        total = max(int(0.97 * host_dur / st) - 3 * warm, warm + 10)
        tasks.append(sim(f"hog-{arch}", K=16, Z=4, total=total, warm=warm,
                         st=st, gpus=gpus, colo=None, rank=0))
    # small tasks: the rank sweep — uniform width, heterogeneous TRUE
    # rank. Each runs ~1/4 of the host's lifetime: under true-rank
    # charges the whole sweep co-trains inside the host window, while
    # r_max-masked charges serialize the replica to ONE guest at a time,
    # spilling the rest past the hogs onto the exclusive tail.
    for i in range(num_small):
        r = RANK_SWEEP[i % len(RANK_SWEEP)]
        total = int(rng.integers(2300, 3100))
        tasks.append(sim(f"small-r{r}-{i}", K=2, Z=2, total=total,
                         warm=max(total // 20, 1),
                         st=step_time(cfg, 2, 2, r, 1), gpus=1, rank=r,
                         colo=sim_colo_spec(RELAXED_KEY, K=2, Z=2,
                                            per_adapter_batch=2, seq_len=SEQ,
                                            lora_rank=r)))
    return tasks


def _with_mode(tasks, mode: str):
    """exclusive: drop colo; rankmasked: strip true ranks (every request
    billed r_max) and step at r_max cost; ranklocal: as built (true-rank
    charges + true-rank step times). Exclusive also steps at r_max cost —
    it IS the padded execution, just unfused."""
    cfg = get_arch(FUSE_ARCH)
    out = []
    for spec, factory, colo, rank in tasks:
        if colo is not None:
            if mode == "ranklocal":
                out.append((spec, factory, colo))
                continue
            # padded execution: r_max step time for host + smalls
            st = step_time(cfg, colo.slots_needed, colo.per_adapter_batch,
                           R_MAX, 1)

            def factory_masked(st=st, f=factory):
                drv = f()
                drv.step_time_s = st
                return drv
            steps_spec = spec.duration / factory().step_time_s
            spec = dataclasses.replace(spec, duration=steps_spec * st)
            colo = None if mode == "exclusive" else dataclasses.replace(
                colo, lora_rank=None)
            out.append((spec, factory_masked, colo))
        else:
            out.append((spec, factory, colo))
    return out


def _solo_area(tasks_mode) -> float:
    """Sum of (solo realized duration x gpus) under this mode's step
    times — the work area effective utilization normalizes."""
    area = 0.0
    for spec, factory, _ in tasks_mode:
        drv = factory()
        drv.start(0.0)
        dur = 0.0
        while True:
            chunk = drv.step_chunk()
            dur += chunk.dt
            if chunk.done:
                break
        area += dur * spec.gpus
    return area


def run_cluster(tasks, G: int) -> dict:
    out = {}
    areas = {}
    for mode in ("exclusive", "rankmasked", "ranklocal"):
        tm = _with_mode(tasks, mode)
        specs = [s for s, _, _ in tm]
        plan = solve(specs, G, "cp")
        plan.validate(G)
        static = execute_static(plan, G, {s.name: f for s, f, _ in tm})
        rt = ElasticClusterRuntime(G, colocate=(mode != "exclusive"))
        for s, f, c in tm:
            rt.submit(s, f, colo=c)
        rep = rt.run(initial=plan)
        assert rep.makespan <= static.makespan + 1e-9, \
            f"{mode} elastic regressed past the static plan"
        out[mode] = rep
        areas[mode] = _solo_area(tm)
        if mode == "exclusive":
            static_mk = static.makespan

    excl, mask, local = out["exclusive"], out["rankmasked"], out["ranklocal"]
    # identical work, attributed identically, across all three strategies
    assert excl.results == mask.results == local.results, \
        "rank budgeting strategy changed task results"
    assert local.colocated, "ranklocal mode fused nothing"
    extra = {n for n in local.colocated if n not in mask.colocated}
    assert extra, "no extra low-rank guest fused — the rank budget is idle"
    assert local.makespan < mask.makespan - 1e-9, \
        "rank-local did not beat rank-masked execution"
    assert mask.makespan <= excl.makespan + 1e-9

    def report(mode, rep) -> dict:
        return {
            "makespan_s": rep.makespan,
            "utilization_effective": areas[mode] / (len(rep.gpu_busy)
                                                    * rep.makespan),
            "gpu_occupancy": rep.utilization,
            "replans": rep.replans,
            "fused_tasks": dict(rep.colocated),
            "fuse_events": sum(1 for e in rep.events
                               if e.kind is EventKind.TASK_FUSED),
            "task_starts": {k: round(v, 4)
                            for k, v in rep.task_starts.items()},
            "task_ends": {k: round(v, 4) for k, v in rep.task_ends.items()},
        }

    excl_r = report("exclusive", excl)
    mask_r = report("rankmasked", mask)
    local_r = report("ranklocal", local)
    assert local_r["utilization_effective"] > \
        mask_r["utilization_effective"] + 1e-9, \
        "rank-local did not lift effective utilization past rank-masked"
    cfg = get_arch(FUSE_ARCH)
    st_masked = step_time(cfg, 2, 2, R_MAX, 1)
    return {
        "G": G,
        "num_tasks": len(tasks),
        "tasks": [{"name": s.name, "gpus": s.gpus,
                   "est_duration_s": round(s.duration, 4),
                   "lora_rank": (r if c is not None else None),
                   "fusable": c is not None}
                  for s, _, c, r in tasks],
        "static_plan_makespan_s": static_mk,
        "exclusive": excl_r,
        "rankmasked": mask_r,
        "ranklocal": local_r,
        "speedup_vs_exclusive": excl.makespan / max(local.makespan, 1e-12),
        "speedup_vs_rankmasked": mask.makespan / max(local.makespan, 1e-12),
        "step_time": {
            "small_rankmasked_s": st_masked,
            "small_by_rank_s": {r: step_time(cfg, 2, 2, r, 1)
                                for r in RANK_SWEEP},
        },
        "adapter_flops_speedup": ranklocal_savings(
            cfg, RANK_SWEEP, tokens_per_slot=2 * SEQ).flop_saving,
    }


def run_isolation_check() -> dict:
    """Real training: tasks with DIFFERENT true ranks (2/4 vs full-rank
    8/8 on an r_max=8 reduced model) fused on one SharedBackboneExecutor
    vs each alone — loss histories bitwise identical, best-vals equal
    (the full-rank host flips dense -> rank-local dispatch and must not
    move a bit)."""
    cfg = dataclasses.replace(
        get_arch("paper-llama-tiny").reduced(num_layers=2, d_model=64,
                                             vocab=128), dtype="float32")
    params = M.init_params(jax.random.PRNGKey(0), cfg)
    ranks = {"A": (2, 4), "B": (8, 8)}
    seeds = {"A": 3, "B": 4}
    datasets = {
        "A": make_task_dataset("rl-a", cfg.vocab_size, seq_len=16,
                               num_train=32, num_val=8, difficulty=0.2,
                               seed=1),
        "B": make_task_dataset("rl-b", cfg.vocab_size, seq_len=16,
                               num_train=32, num_val=8, difficulty=0.6,
                               seed=2),
    }

    def run(names):
        ex = SharedBackboneExecutor(cfg, params, Z=4, per_adapter_batch=2,
                                    eval_every=2, seed=0)
        lcs = []
        for name in names:
            jobs = {f"{name}/j{k}": TrainConfig(
                learning_rate=lr, lora_rank=rk, max_steps=8,
                per_adapter_batch=2)
                for k, (lr, rk) in enumerate(zip((3e-3, 1e-3),
                                                 ranks[name]))}
            lcs.append(TaskLifecycle(
                ex, name, jobs, 8,
                ee=EarlyExitConfig(warmup_ratio=0.25, select_ratio=1.0),
                max_slots=2,
                batcher=SlotBatcher(datasets[name], 2, 2,
                                    seed=seeds[name]),
                seed=seeds[name]))
        results = run_colocated(ex, lcs)
        hists = {lc.task_name: {j: (tuple(m.val_hist),
                                    tuple(m.raw_train_hist))
                                for j, m in lc.monitors.items()}
                 for lc in lcs}
        return results, hists

    fused, fused_h = run(["A", "B"])
    out = {}
    for name in ("A", "B"):
        solo, solo_h = run([name])
        bitwise = fused_h[name] == solo_h[name]
        identical = fused[name].best_val == solo[name].best_val
        out[name] = {"ranks": list(ranks[name]),
                     "solo_best_val": solo[name].best_val,
                     "fused_best_val": fused[name].best_val,
                     "losses_bitwise_identical": bitwise,
                     "best_val_identical": identical}
        assert bitwise, f"different-rank guest perturbed {name}'s losses"
        assert identical, f"rank-local fusion changed task {name}'s best-val"
    return out


def run_kernel_check(smoke: bool) -> dict:
    """ranks==r_max bitwise vs dense, plus interpret-mode wall time of a
    mixed-rank fwd+VJP (observability only — interpret mode runs the grid
    on host)."""
    Z, T, d, r_max = 4, (64 if smoke else 128), (128 if smoke else 256), 64
    ks = jax.random.split(jax.random.PRNGKey(0), 4)
    x = jax.random.normal(ks[0], (Z, T, d))
    A = 0.1 * jax.random.normal(ks[1], (Z, d, r_max))
    B = 0.1 * jax.random.normal(ks[2], (Z, r_max, d))
    scale = jnp.ones((Z,))
    ranks = jnp.asarray([4, 8, 16, 64], jnp.int32)
    full = jnp.full((Z,), r_max, jnp.int32)
    dense = kops.grouped_lora(x, A, B, scale, interpret=True)
    rl_full = kops.ranklocal_grouped_lora(x, A, B, scale, full,
                                          interpret=True)
    bitwise = bool((np.asarray(dense) == np.asarray(rl_full)).all())
    assert bitwise, "ranks==r_max is not bitwise-equal to the dense path"

    def bench(fn, iters=2):
        g = jax.jit(jax.grad(lambda a, b: jnp.sum(fn(a, b) ** 2),
                             argnums=(0, 1)))
        out = g(A, B)
        jax.block_until_ready(out)
        t0 = time.time()
        for _ in range(iters):
            out = g(A, B)
        jax.block_until_ready(out)
        return (time.time() - t0) / iters

    t_dense = bench(lambda a, b: kops.grouped_lora(x, a, b, scale,
                                                   interpret=True))
    t_local = bench(lambda a, b: kops.ranklocal_grouped_lora(
        x, a, b, scale, ranks, interpret=True))
    return {"full_rank_bitwise_equal_dense": bitwise,
            "interpret_fwd_vjp_dense_s": t_dense,
            "interpret_fwd_vjp_ranklocal_s": t_local,
            "mixed_ranks": [int(v) for v in ranks]}


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__)
    ap.add_argument("--smoke", action="store_true",
                    help="small instance (CI)")
    ap.add_argument("--gpus", type=int, default=4)
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--out", default="BENCH_ranklocal.json")
    args = ap.parse_args(argv)

    # the cluster phase is virtual-time (cheap) and the rank-masked
    # serialization only binds once the sweep outgrows the host window,
    # so smoke keeps the full 10-task sweep and shrinks the real-training
    # and kernel phases instead
    tasks = build_workload(num_small=10, seed=args.seed)
    result = run_cluster(tasks, args.gpus)
    result["isolation"] = run_isolation_check()
    result["kernel"] = run_kernel_check(args.smoke)

    with open(args.out, "w") as f:
        json.dump(result, f, indent=2)
    for mode in ("exclusive", "rankmasked", "ranklocal"):
        r = result[mode]
        print(f"{mode:10s} makespan : {r['makespan_s']:.3f}s "
              f"(eff util {r['utilization_effective']:.2%}, "
              f"{r['fuse_events']} fused)")
    print(f"speedup vs rankmasked: {result['speedup_vs_rankmasked']:.2f}x "
          f"(vs exclusive {result['speedup_vs_exclusive']:.2f}x); "
          f"adapter flops x{result['adapter_flops_speedup']:.2f}")
    iso = result["isolation"]
    print("isolation            : " + ", ".join(
        f"{n}(r={v['ranks']}) best_val {v['fused_best_val']:.4f} "
        f"({'bitwise' if v['losses_bitwise_identical'] else 'DIFFERS'})"
        for n, v in iso.items()))
    print(f"wrote {args.out}")
    return 0


if __name__ == "__main__":
    sys.exit(main())
