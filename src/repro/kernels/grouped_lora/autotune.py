"""Tile-plan autotuning for the grouped-LoRA kernel family.

A ``TilePlan`` names one candidate block shape ``(BT, BM, BN, BK)``
for the grouped-LoRA kernel family; the autotuner enumerates the
Mosaic-legal candidates for a ``(d_in, d_out, r_max, Z, token-bucket)``
key, times each on the six rank-local kernels (fwd S=XA / Y=SB and the
four bwd kernels) via ``profiler.measure_throughput`` (warmup +
median-of-repeats, so winners aren't picked off compile time or timer
noise), and caches the winner twice: in-process (like ``ops._tile_plan``)
and durably through ``ProfileStore.put_spec(..., durable=True)`` so later
sessions skip the sweep.

**The bitwise contract.** Tuned plans must produce outputs bitwise
identical to the default constants (the executor's fused-vs-solo and
migration proofs lean on bit-stable kernels). Tiling a *parallel* grid
dimension only re-partitions independent output tiles — same per-element
contraction, same fp32 accumulation order — but tiling a *contraction*
dimension regroups the fp32 sums. Each block field therefore tunes only
where its axis is parallel:

  * ``bm`` (token rows) and ``bn`` (output features) are parallel in every
    kernel they touch — freely tunable;
  * the rank axis is never tiled: every block holds the whole padded
    rank (Mosaic's (8, 128) rule, and every configured ``r_max`` fits one
    128-lane MXU pass);
  * ``bk`` / ``bt`` are pure contraction blocks (d_in/d_out resp. token
    contraction) — candidates pin them to the default grouping. They stay
    in the plan so a future parity-level (TPU, non-bitwise) sweep can
    open them without an interface change.

The sweep *verifies* the contract per candidate — all six kernel outputs
are compared bitwise against the default plan's on the probe operands and
non-identical candidates are discarded — so the winner is bitwise-equal by
construction, not by hope. The default plan always competes, so the tuned
plan is never slower than the default on the probe.

interpret=True times the CPU interpret-mode harness; on TPU the same
sweep times Mosaic lowerings.
"""
from __future__ import annotations

import dataclasses
from typing import Dict, List, Optional, Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from repro.kernels.grouped_lora import grouped_lora as K
from repro.kernels.grouped_lora import ranklocal as RL

_LANE = 128   # MXU lane width: last-dim block unit
_SUB = 8      # fp32 sublane: second-to-last-dim block unit

PLAN_SPEC_VERSION = 2   # 2: the rank axis is no longer a plan field


@dataclasses.dataclass(frozen=True)
class TilePlan:
    """One candidate block shape for the grouped-LoRA kernel family.

    Field roles (see module docstring for the bitwise rationale):
    ``bm`` token-row block, ``bn`` output-feature block, ``bk`` feature
    contraction block, ``bt`` token contraction block (weight grads)."""
    bm: int = K.BM
    bn: int = K.BN
    bk: int = K.BK
    bt: int = K.BT

    def to_json(self) -> Dict[str, int]:
        return {"version": PLAN_SPEC_VERSION, "bm": self.bm, "bn": self.bn,
                "bk": self.bk, "bt": self.bt}

    @classmethod
    def from_json(cls, d: Dict) -> Optional["TilePlan"]:
        if not isinstance(d, dict) or d.get("version") != PLAN_SPEC_VERSION:
            return None
        return cls(bm=int(d["bm"]), bn=int(d["bn"]), bk=int(d["bk"]),
                   bt=int(d["bt"]))


DEFAULT_PLAN = TilePlan()


def token_bucket(tokens: int) -> int:
    """Round a token count up to the next power of two (floor ``_SUB``):
    nearby fused-step widths share one tuned plan instead of sweeping per
    exact T."""
    b = _SUB
    while b < tokens:
        b *= 2
    return b


def plan_key(d_in: int, d_out: int, r_max: int, Z: int,
             tokens: int) -> Tuple:
    """The autotune cache key — flat JSON-representable tuple, shared by
    the in-process cache and the ProfileStore durable-spec layer."""
    return ("tile_plan", PLAN_SPEC_VERSION, int(d_in), int(d_out),
            int(r_max), int(Z), token_bucket(int(tokens)))


def padded_dims(tokens: int, d_in: int, d_out: int,
                r_max: int) -> Tuple[int, int, int, int]:
    """(Tp, dinp, doutp, rp) the ops wrapper pads operands to — blocks
    must divide these, not the raw shapes."""
    from repro.kernels.grouped_lora import ops
    return ops._tile_plan(tokens, d_in, d_out, r_max)


def _divides(block: int, dim: int) -> bool:
    """A block is grid-legal for a dim if it covers it whole (the kernel
    wrappers ``min()`` it down) or divides it exactly — a non-divisor
    below the dim would silently drop tiles (``dim // block`` floors)."""
    return block >= dim or dim % block == 0


def is_legal(plan: TilePlan, tokens: int, d_in: int, d_out: int,
             r_max: int) -> bool:
    """Mosaic legality of a plan for one shape key: every field a positive
    multiple of its axis unit (sublane 8 for token axes, lane 128 for
    feature axes) and grid-exact against the padded dims on every axis it
    tiles (``bn``/``bk`` touch BOTH d_in and d_out)."""
    Tp, dinp, doutp, _ = padded_dims(tokens, d_in, d_out, r_max)
    if min(plan.bm, plan.bn, plan.bk, plan.bt) <= 0:
        return False
    if plan.bm % _SUB or plan.bt % _SUB:
        return False
    if plan.bn % _LANE and plan.bn < min(dinp, doutp):
        return False
    if plan.bk % _LANE and plan.bk < min(dinp, doutp):
        return False
    return (_divides(plan.bm, Tp) and _divides(plan.bt, Tp)
            and _divides(plan.bn, dinp) and _divides(plan.bn, doutp)
            and _divides(plan.bk, dinp) and _divides(plan.bk, doutp))


def _axis_choices(dim: int, unit: int, cap: int) -> List[int]:
    """Unit-multiples that exactly divide ``dim`` (ascending, <= cap),
    plus ``dim`` itself — the one-tile-covers-all candidate."""
    out = [b for b in range(unit, min(dim, cap) + 1, unit)
           if dim % b == 0]
    if dim not in out:
        out.append(dim)
    return out


def candidate_plans(tokens: int, d_in: int, d_out: int, r_max: int,
                    max_candidates: int = 12) -> List[TilePlan]:
    """Legal candidate block shapes for one shape key.

    ``bm`` sweeps sublane-multiple divisors of the padded token dim,
    ``bn`` lane-multiple divisors legal for BOTH feature dims.
    ``bk``/``bt`` are pinned to the defaults (contraction grouping — the
    bitwise contract, module docstring). The default plan is always
    candidate 0; the rest are evenly subsampled down to
    ``max_candidates``."""
    Tp, dinp, doutp, _ = padded_dims(tokens, d_in, d_out, r_max)
    bms = _axis_choices(Tp, _SUB, 256)
    bns = [b for b in _axis_choices(doutp, _LANE, 1024)
           if _divides(b, dinp)]
    if not bns:
        bns = [K.BN]
    plans: List[TilePlan] = [DEFAULT_PLAN]
    for bm in bms:
        for bn in bns:
            p = TilePlan(bm=bm, bn=bn)
            if p != DEFAULT_PLAN and is_legal(p, tokens, d_in, d_out, r_max):
                plans.append(p)
    if len(plans) > max_candidates:
        rest = plans[1:]
        stride = len(rest) / (max_candidates - 1)
        plans = [plans[0]] + [rest[int(i * stride)]
                              for i in range(max_candidates - 1)]
    return plans


# ---------------------------------------------------------------------------
# The sweep: time each candidate on the six rank-local kernels
# ---------------------------------------------------------------------------

def _probe_operands(Z: int, tokens: int, d_in: int, d_out: int, r_max: int,
                    seed: int = 0):
    """Representative operands: mixed true ranks (so the rank masks are
    exercised) and a ragged row tail."""
    ks = jax.random.split(jax.random.PRNGKey(seed), 4)
    x = jax.random.normal(ks[0], (Z, tokens, d_in), jnp.float32)
    A = 0.1 * jax.random.normal(ks[1], (Z, d_in, r_max), jnp.float32)
    B = 0.1 * jax.random.normal(ks[2], (Z, r_max, d_out), jnp.float32)
    dy = jax.random.normal(ks[3], (Z, tokens, d_out), jnp.float32)
    scale = jnp.ones((Z,), jnp.float32)
    sweep = [r for r in (r_max // 8, r_max // 4, r_max // 2, r_max) if r > 0]
    ranks = jnp.asarray([max(_SUB, sweep[z % len(sweep)])
                         for z in range(Z)], jnp.int32)
    rows = jnp.asarray([tokens if z % 2 == 0 else max(tokens // 2, 1)
                        for z in range(Z)], jnp.int32)
    return x, A, B, dy, scale, rows, ranks


def six_kernel_step(plan: TilePlan, interpret: bool = True):
    """A jitted function running all six rank-local kernels under one
    plan — the autotuner's unit of timing AND of bitwise comparison."""

    def step(x, A, B, dy, scale, rows, ranks):
        s = RL.xa(x, A, rows, ranks, bm=plan.bm, bk=plan.bk,
                  interpret=interpret)
        y = RL.sb_add(s, B, scale, rows, ranks, bm=plan.bm, bn=plan.bn,
                      interpret=interpret)
        ds_ = RL.ds(dy, B, scale, rows, ranks, bm=plan.bm, bk=plan.bk,
                    interpret=interpret)
        dx_ = RL.dx(ds_, A, rows, ranks, bm=plan.bm, bn=plan.bn,
                    interpret=interpret)
        dA_ = RL.da(x, ds_, rows, ranks, bd=plan.bn, bt=plan.bt,
                    interpret=interpret)
        dB_ = RL.db(s, dy, scale, rows, ranks, bn=plan.bn, bt=plan.bt,
                    interpret=interpret)
        return s, y, ds_, dx_, dA_, dB_

    return jax.jit(step)


def kernel_family_flops(Z: int, tokens: int, d_in: int, d_out: int,
                        r_max: int) -> float:
    """Dense-equivalent MAC*2 count of the six kernels (normalization for
    throughput reporting; identical across candidates so ratios hold)."""
    fwd = 2.0 * Z * tokens * r_max * (d_in + d_out)
    bwd = 2.0 * fwd      # ds+dx+dA+dB mirror the two fwd GEMMs twice over
    return fwd + bwd


@dataclasses.dataclass
class CandidateTiming:
    plan: TilePlan
    seconds: float
    bitwise_equal_default: bool


@dataclasses.dataclass
class TuneResult:
    """Everything the bench/report layers need from one sweep."""
    key: Tuple
    plan: TilePlan                      # the winner
    default_s: float
    best_s: float
    flops: float
    candidates: List[CandidateTiming]

    @property
    def speedup(self) -> float:
        return self.default_s / max(self.best_s, 1e-12)

    @property
    def default_flops_per_s(self) -> float:
        return self.flops / max(self.default_s, 1e-12)

    @property
    def tuned_flops_per_s(self) -> float:
        return self.flops / max(self.best_s, 1e-12)


def sweep(d_in: int, d_out: int, r_max: int, Z: int = 4,
          tokens: int = 128, *, interpret: bool = True,
          max_candidates: int = 12, iters: int = 2, repeats: int = 3,
          seed: int = 0) -> TuneResult:
    """Time every legal candidate on the six kernels; return the fastest
    bitwise-equal-to-default candidate (the default itself competes, so
    the winner is never slower than default on the probe)."""
    from repro.sched.profiler import measure_throughput
    args = _probe_operands(Z, tokens, d_in, d_out, r_max, seed)
    plans = candidate_plans(tokens, d_in, d_out, r_max, max_candidates)
    baseline = jax.tree_util.tree_map(
        np.asarray, six_kernel_step(DEFAULT_PLAN, interpret)(*args))
    timings: List[CandidateTiming] = []
    default_s = best_s = None
    best: TilePlan = DEFAULT_PLAN
    for plan in plans:
        fn = six_kernel_step(plan, interpret)
        outs = jax.tree_util.tree_map(np.asarray, fn(*args))
        bitwise = all(o.tobytes() == b.tobytes()
                      for o, b in zip(outs, baseline))
        prof = measure_throughput(fn, args, total_batch=Z,
                                  iters=iters, repeats=repeats)
        timings.append(CandidateTiming(plan, prof.step_time_s, bitwise))
        if plan == DEFAULT_PLAN:
            default_s = prof.step_time_s
        if bitwise and (best_s is None or prof.step_time_s < best_s):
            best_s, best = prof.step_time_s, plan
    assert default_s is not None and best_s is not None
    return TuneResult(key=plan_key(d_in, d_out, r_max, Z, tokens),
                      plan=best, default_s=default_s, best_s=best_s,
                      flops=kernel_family_flops(Z, tokens, d_in, d_out,
                                                r_max),
                      candidates=timings)


# ---------------------------------------------------------------------------
# Cached entry point: in-process + ProfileStore-durable winners
# ---------------------------------------------------------------------------

_PLANS: Dict[Tuple, TilePlan] = {}


def clear_plan_cache() -> None:
    """Drop the in-process winner cache (tests)."""
    _PLANS.clear()


def autotune_tile_plan(d_in: int, d_out: int, r_max: int, Z: int = 4,
                       tokens: int = 128, *, interpret: bool = True,
                       store=None, max_candidates: int = 12,
                       iters: int = 2, repeats: int = 3,
                       seed: int = 0) -> TilePlan:
    """The tuned plan for a shape key, cheapest source first: in-process
    cache -> ProfileStore durable spec (a previous session's sweep) ->
    fresh sweep (then persisted through both). ``store`` is a
    ``ProfileStore`` or None (no cross-session persistence)."""
    key = plan_key(d_in, d_out, r_max, Z, tokens)
    hit = _PLANS.get(key)
    if hit is not None:
        return hit
    if store is not None:
        spec = store.get_spec(key)
        plan = TilePlan.from_json(spec) if spec is not None else None
        if plan is not None and is_legal(plan, tokens, d_in, d_out, r_max):
            _PLANS[key] = plan
            return plan
    result = sweep(d_in, d_out, r_max, Z, tokens, interpret=interpret,
                   max_candidates=max_candidates, iters=iters,
                   repeats=repeats, seed=seed)
    _PLANS[key] = result.plan
    if store is not None:
        store.put_spec(key, result.plan.to_json(), durable=True)
    return result.plan


def plan_for(shapes: Sequence[int], *, store=None,
             interpret: bool = True) -> TilePlan:
    """Convenience: ``shapes = (Z, tokens, d_in, d_out, r_max)`` — the
    executor-facing signature."""
    Z, tokens, d_in, d_out, r_max = shapes
    return autotune_tile_plan(d_in, d_out, r_max, Z, tokens,
                              interpret=interpret, store=store)
