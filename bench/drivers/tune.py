"""Tuning driver: one task's hyperparameter sweep through the executor the
engine builds (``Engine._make_executor``), driven chunk by chunk through
``run_task_chunks`` -- the device path the tuning service steps, without
its virtual clock.

Set-up builds the executor, compiles every train-step and eval-step
variant the mix can produce (with and without ``slot_ranks`` and
``slot_rows``; run with every slot inactive, which leaves the state as it
was) and what a recorded admission runs, and runs the first chunk.

Two sets of slots are recorded at the executor's own train-step call
(``Record``), each for its first ``check_steps`` steps: the first wave,
from step 1 in set-up, and the first job admitted inside the window into
a slot that another job held (a wave rotation or a refill). At its first
step each recorded slot gets the benchmark's own adapter (B random, so
the adapters count from the first step) in place of the one the program
made, whose B has to be zero; its optimizer state, rank and width stay
the program's. Kept are the rows fed, the per-slot losses, the optimizer
state after the first step and the adapter the ``check_steps + 1``-th
step receives.

The window then runs the mix's ``window_chunks`` chunks: a fixed amount
of work, sized to last about the benchmark's ``run_seconds`` on the
program as it is (a window that ended with the first chunk after a time
would take one chunk more or less as timing wobbles, and a chunk with an
eval in it moves the rate by a third). ``tune_tokens_per_s`` is every
real (non-pad) token trained in it over the whole window, evaluation,
exits and refills included.

After the window the program's state is freed and the float32 reference
follows each recorded slot through the same steps from the same adapters
and rows.
"""
from __future__ import annotations

import functools
import itertools
from typing import Dict, List, Optional

import numpy as np

from bench import flops, harness, weights, workload
from bench.harness import BenchError, Run

# the mix's AdamW constants -> the TrainConfig field each job carries
ADAMW = {"wd": "weight_decay", "beta1": "beta1", "beta2": "beta2",
         "grad_clip": "grad_clip"}


@functools.lru_cache(maxsize=None)
def _slot_ops():
    """Jitted ops on a slot-axis tree ([L, Z, ...] leaves) at slot indices
    ``idx``: ``put`` writes one adapter per index (the tree is donated),
    ``take`` copies those slots out, ``b_abs`` sums |B| over them. Each
    compiles once per number of indices."""
    import jax
    import jax.numpy as jnp

    def put(tree, sub, idx):
        return jax.tree_util.tree_map(lambda x, a: x.at[:, idx].set(a),
                                      tree, sub)

    def take(tree, idx):
        return jax.tree_util.tree_map(lambda x: x[:, idx], tree)

    def b_abs(tree, idx):
        return sum(jnp.sum(jnp.abs(ab["B"][:, idx])) for ab in tree.values())

    return (jax.jit(put, donate_argnums=0), jax.jit(take), jax.jit(b_abs))


class Record:
    """The first ``k`` steps of some slots' jobs, from the adapters the
    benchmark puts in at their first step (``weights.adapters`` with
    ``stream``): the rows fed, the per-slot losses, Adam's first moment
    after the first step, the adapters the ``k + 1``-th step receives, and
    the sum of |B| of the adapters the program itself had put there (a
    fresh job's B is zero)."""

    def __init__(self, call: int, slots: List[int], occupied: List,
                 k: int, stream: int, host: bool):
        self.call0, self.k, self.stream, self.host = call, k, stream, host
        self.slots = slots
        self.jobs = [occupied[z] for z in slots]      # (job, rank)
        self.idx = np.asarray(slots, np.int32)
        self.batches: List[Dict[str, np.ndarray]] = []
        self.losses: List = []
        self.mu1 = self.after = self.b_abs = None

    @property
    def done(self) -> bool:
        return self.after is not None

    def before(self, call: int, lora, batch, occupied, spec, seed):
        """Called before the program's step number ``call``; returns the
        adapters the step is to receive."""
        i = call - self.call0
        if i < 0 or self.done:
            return lora
        if [occupied[z] for z in self.slots] != self.jobs:
            raise BenchError(f"recorded slots {self.slots} changed jobs "
                             f"within {self.k} steps: {self.jobs} -> "
                             f"{[occupied[z] for z in self.slots]}")
        put, take, b_abs = _slot_ops()
        if i == 0:
            self.b_abs = b_abs(lora, self.idx)
            ours = weights.adapters(spec, [r for _, r in self.jobs], seed,
                                    self.stream)
            lora = put(lora, ours, self.idx)
        if i < self.k:
            self.batches.append({key: np.asarray(batch[key])[self.idx]
                                 for key in ("tokens", "labels")})
        else:
            self.after = self._keep(take(lora, self.idx))
        return lora

    def _keep(self, tree):
        """A copy on the device, or on the host where the copy is not
        timed (the first wave's, in set-up)."""
        import jax
        return jax.tree_util.tree_map(np.asarray, tree) if self.host else tree

    def after_step(self, call: int, out) -> None:
        i = call - self.call0
        if i == 0:
            self.mu1 = self._keep(_slot_ops()[1](out[1].mu, self.idx))
        if 0 <= i < self.k:
            self.losses.append(out[2]["per_slot_loss"])

    def fetch(self) -> None:
        """Bring what was kept on the device to the host."""
        import jax
        self.mu1, self.after = jax.tree_util.tree_map(
            np.asarray, (self.mu1, self.after))
        self.losses = [np.asarray(x)[self.idx] for x in self.losses]
        self.b_abs = float(self.b_abs)


class StepRecorder:
    """Stands in for the executor's jitted train step and calls it: records
    what the correctness comparison needs and counts the window's work.

    Two sets of slots are recorded. The first wave: every occupied slot
    from the first step. In the window, the first job admitted into a slot
    that an earlier job held (a wave rotation or a refill): the admitted
    slot whose rank moved most from its former occupant's, the lowest on a
    tie. Both start from the benchmark's own adapters (B random, so the
    adapters count from the first step)."""

    def __init__(self, step, backbone, spec: Dict, seed: int,
                 check_steps: int):
        self.step = step
        self.bb = backbone
        self.spec = spec
        self.seed = seed
        self.k = check_steps
        self.calls = 0
        self.first: Optional[Record] = None
        self.admitted: Optional[Record] = None
        self.prev: List = []
        self.seen = set()
        self.counting = False
        self.count = dict(steps=0, real_tokens=0, positions=0,
                          model_flops=0.0, failed=0)
        self.window_losses: List = []     # (device losses, live slots)

    def _admission(self, occupied: List) -> Optional[int]:
        fresh = [z for z, (job, _) in enumerate(occupied)
                 if job is not None and job not in self.seen
                 and self.prev[z][0] is not None]
        if not fresh:
            return None
        return max(fresh, key=lambda z: (abs(occupied[z][1]
                                             - self.prev[z][1]), -z))

    def __call__(self, params, lora, opt, hp, active, ranks, batch):
        self.calls += 1
        n = self.calls
        occupied = list(zip(self.bb.slots.slot_jobs, self.bb.slots.slot_rank))
        if n == 1:
            live = [z for z, (job, _) in enumerate(occupied) if job is not None]
            self.first = Record(n, live, occupied, self.k, stream=1,
                                host=True)
        elif self.counting and self.admitted is None:
            z = self._admission(occupied)
            if z is not None:
                self.admitted = Record(n, [z], occupied, self.k, stream=2,
                                       host=False)
        for rec in (self.first, self.admitted):
            if rec is not None:
                lora = rec.before(n, lora, batch, occupied, self.spec,
                                  self.seed)
        with harness.annotate("tune.train_step"):
            out = self.step(params, lora, opt, hp, active, ranks, batch)
        for rec in (self.first, self.admitted):
            if rec is not None:
                rec.after_step(n, out)
        self.prev = occupied
        self.seen.update(job for job, _ in occupied if job is not None)
        if self.counting:
            self._count(batch, out)
        return out

    def _count(self, batch, out) -> None:
        c = self.count
        S = batch["tokens"].shape[-1]
        c["steps"] += 1
        c["positions"] += int(np.prod(batch["tokens"].shape))
        live = []
        for z, job in enumerate(self.bb.slots.slot_jobs):
            if job is None:
                continue
            tok = self.bb.slots.slot_b[z] * (self.bb.slots.slot_seq[z] or S)
            c["real_tokens"] += tok
            c["model_flops"] += tok * flops.train_flops_per_token(
                self.spec, self.bb.slots.slot_seq[z] or S,
                self.bb.slots.slot_rank[z])
            live.append(z)
        # read after the window: a fetch here would add a host sync per
        # step that the program may not make
        self.window_losses.append((out[2]["per_slot_loss"], live))

    def count_failed(self) -> None:
        """Steps of the window with a non-finite loss in a live slot."""
        self.count["failed"] = sum(
            not np.all(np.isfinite(np.asarray(loss)[live]))
            for loss, live in self.window_losses)
        self.window_losses = []


def annotated(fn, name: str):
    """``fn`` inside a host span of the trace: the idle time under it is
    the dispatch of that call."""
    def call(*args):
        with harness.annotate(name):
            return fn(*args)
    return call


def warm_variants(ex, traffic: Dict, r_max: int) -> None:
    """Compile (or load) every train-step and eval-step variant the mix
    can produce, on the executor's own jitted steps, with every slot
    inactive: the optimizer then moves nothing."""
    import jax
    import jax.numpy as jnp
    bb = ex.backbone
    Z, b, S = bb.Z, bb.b_cap, bb.seq_cap
    space = traffic["search_space"]
    ranks = space.get("rank", [16])
    widths = space.get("batch_size", [b])
    rank_opts = {r < r_max for r in ranks} | ({False} if any(
        r >= r_max for r in ranks) else set())
    row_opts = {w < b for w in widths} | ({False} if b in widths else set())
    tokens = jnp.zeros((Z, b, S), jnp.int32)
    idle = jnp.zeros((Z,), jnp.int32)
    rank_vec = jnp.full((Z,), min(ranks), jnp.int32)
    for with_ranks, with_rows in itertools.product(sorted(rank_opts),
                                                   sorted(row_opts)):
        batch = {"tokens": tokens, "labels": tokens}
        if with_rows:
            batch["slot_rows"] = jnp.full((Z,), b * S, jnp.int32)
        if with_ranks:
            batch["slot_ranks"] = rank_vec
        bb.slots.lora, bb.slots.opt_state, m = bb._train_step(
            bb.params, bb.slots.lora, bb.slots.opt_state, bb.slots.hp,
            idle, bb.slots.ranks, batch)
        jax.block_until_ready(m)
    for with_ranks in sorted(rank_opts):
        batch = {"tokens": tokens, "labels": tokens}
        if with_ranks:
            batch["slot_ranks"] = rank_vec
        jax.block_until_ready(bb._eval_step(bb.params, bb.slots.lora, idle,
                                            batch))
    # the scalar reads a slot snapshot makes at every wave rotation
    int(bb.slots.ranks[0]), int(bb.slots.opt_state.count[0])


def warm_admission(bb, spec: Dict, seed: int) -> None:
    """Compile (or load) what ``Record`` runs at a job admitted inside the
    window, for one slot: the adapter builder, ``put`` (into slot 0, which
    is empty: the first admission writes it anew), ``take`` and
    ``b_abs``."""
    import jax
    import jax.numpy as jnp
    put, take, b_abs = _slot_ops()
    idx = np.zeros((1,), np.int32)
    ours = weights.adapters(spec, [1], seed, stream=2)
    bb.slots.lora = put(bb.slots.lora, ours, idx)
    jax.block_until_ready((take(bb.slots.lora, idx), b_abs(bb.slots.lora,
                                                            idx)))
    del ours
    jnp.zeros(()).block_until_ready()


def run(ctx: harness.Context, t_start: float) -> Run:
    import jax
    from repro.core.engine import EarlyExit, Engine, Task
    from repro.data.synthetic import TaskDataset
    from repro.models import model as M

    tr, spec, cfg = ctx.traffic, ctx.spec, ctx.cfg
    run = Run()
    program_seed = ctx.seed & 0xFFFFFFFF
    params = weights.make_params(spec, ctx.seed)
    weights.check_layout(params, jax.eval_shape(
        lambda: M.init_params(jax.random.PRNGKey(0), cfg)))
    rows = workload.tune_rows(tr, ctx.seed)
    ds = TaskDataset(name=ctx.workload, train=rows["train"], val=rows["val"],
                     vocab_size=cfg.vocab_size, seed=program_seed)
    task = Task(model=cfg, dataset=ds, search_space=tr["search_space"],
                max_steps=tr["max_steps"], num_slots=tr.get("num_slots", 0),
                seed=program_seed,
                name=ctx.workload)
    jobs = task.jobs()
    for tc in jobs.values():
        for k, field in ADAMW.items():
            if getattr(tc, field) != tr["adamw"][k]:
                raise BenchError(f"job {tc.label()}: {field} "
                                 f"{getattr(tc, field)} is not the mix's "
                                 f"{tr['adamw'][k]}")

    class BenchEngine(Engine):
        def base_params(self, cfg, seed=0):
            return params

    engine = BenchEngine(total_gpus=1, eval_every=tr["eval_every"])
    ex = engine._make_executor(task, EarlyExit(
        warmup_ratio=tr["warmup_ratio"]))
    bb = ex.backbone
    warm_variants(ex, tr, cfg.lora.r_max)
    warm_admission(bb, spec, ctx.seed)
    rec = StepRecorder(bb._train_step, bb, spec, ctx.seed,
                       tr["check_steps"])
    bb._train_step = rec
    bb._eval_step = annotated(bb._eval_step, "tune.eval_step")
    gen = ex.run_task_chunks(task.task_name, jobs, task.max_steps)
    next(gen)                                   # the first chunk
    if not rec.first.done:
        raise BenchError("the first chunk ran fewer than "
                         f"{tr['check_steps'] + 1} steps")
    rec.first.fetch()

    rec.counting = True
    chunks, tokens_reported = 0, 0
    with ctx.window(run):
        t0 = harness.clock()
        run.e2e["setup_s"] = t0 - t_start
        while True:
            with harness.annotate("tune.chunk"):
                try:
                    report = next(gen)
                except StopIteration:
                    raise BenchError("the sweep ended inside the window; "
                                     "the mix is too short")
            chunks += 1
            tokens_reported += report.tokens_executed
            if chunks == tr["window_chunks"]:
                break
        t1 = harness.clock()
    rec.counting = False
    rec.count_failed()
    ctx.read_peak(run)
    if rec.admitted is None or not rec.admitted.done:
        raise BenchError(f"the window admitted no job that ran "
                         f"{tr['check_steps'] + 1} steps in it")
    rec.admitted.fetch()
    c = rec.count
    if c["real_tokens"] != tokens_reported:
        raise BenchError(f"the executor reports {tokens_reported} tokens, "
                         f"the benchmark counted {c['real_tokens']}")
    window = t1 - t0
    run.e2e["tune_tokens_per_s"] = c["real_tokens"] / window / ctx.chips
    run.attempted, run.failed = c["steps"], c["failed"]
    run.counters.update(c, window_s=window, chunks=chunks,
                        chips=ctx.chips, Z=bb.Z,
                        admitted_slot=rec.admitted.slots[0],
                        admitted_at_step=rec.admitted.call0)

    # the program's state leaves the chip before the reference runs
    records = [rec.first, rec.admitted]
    del gen, ex, bb, rec, engine
    harness.free()
    t_ref = harness.clock()
    compare(ctx, run, params, jobs, records)
    run.counters["reference_s"] = harness.clock() - t_ref
    return run


def leaf_norms(tree: Dict, z: Optional[int] = None) -> Dict[str, float]:
    """{"<target>.<A|B>": norm} of one adapter (slot ``z`` of a
    slot-axis tree, or a single adapter)."""
    out = {}
    for t, ab in tree.items():
        for m in ("A", "B"):
            x = np.asarray(ab[m], np.float64)
            if z is not None:
                x = x[:, z]
            out[f"{t}.{m}"] = float(np.linalg.norm(x))
    return out


def worst_leaf_gap(prog: Dict[str, float], ref: Dict[str, float],
                   counted: List[str]) -> float:
    """The largest gap between the program's and the reference's norm of
    a leaf, over the reference's norm of that leaf or of the median leaf,
    whichever is larger."""
    med = float(np.median([ref[k] for k in counted]))
    return max(abs(prog[k] - ref[k]) / max(ref[k], med) for k in counted)


def readings(prog: Dict, ref: Dict) -> Dict[str, float]:
    """The numbers compared, of one side against the reference. Each side
    is {"losses": {(slot, step): loss}, "grad": {leaf: norm}, "change":
    {leaf: norm}} over the recorded slots; leaves are "<slot>.<target>.
    <A|B>". Leaves whose reference gradient is nought to rounding move by
    round-off alone: counted are those above a thousandth of the median
    leaf's.

    The losses are compared by their mean relative gap over the recorded
    slots and steps, not the widest: a rank-64 adapter (its LoRA path as
    large as the activations it adds to) rounds its loss in bfloat16 three
    times as far as a rank-8 one, so the widest gap of a sound run reached
    half of what the float8 control reads, while the mean keeps them
    apart by five times or more."""
    med = float(np.median(list(ref["grad"].values())))
    counted = [n for n, v in ref["grad"].items() if v >= 1e-3 * med]
    return {
        "loss_mean_rel_gap": float(np.mean([
            abs(prog["losses"][k] - v) / abs(v)
            for k, v in ref["losses"].items()])),
        "grad_leaf_gap": worst_leaf_gap(prog["grad"], ref["grad"], counted),
        "change_leaf_gap": worst_leaf_gap(prog["change"], ref["change"],
                                          counted),
        "leaves_counted": len(counted), "leaves": len(ref["grad"]),
    }


def side(label: str, losses, g1: Dict, after: Dict, a0: Dict) -> Dict:
    """One recorded slot's share of a side: its losses, first-gradient
    leaf norms and leaf norms of its change over the recorded steps."""
    delta = {t: {m: np.asarray(after[t][m], np.float64)
                 - np.asarray(a0[t][m], np.float64) for m in ab}
             for t, ab in a0.items()}
    return {"losses": {(label, i): float(v) for i, v in enumerate(losses)},
            "grad": {f"{label}.{n}": v for n, v in leaf_norms(g1).items()},
            "change": {f"{label}.{n}": v
                       for n, v in leaf_norms(delta).items()}}


def merge(parts: List[Dict]) -> Dict:
    return {k: {kk: vv for p in parts for kk, vv in p[k].items()}
            for k in ("losses", "grad", "change")}


def compare(ctx, run: Run, params, jobs, records: List[Record]) -> None:
    """The reference follows every recorded slot through its recorded
    steps from the same adapters and rows; the program's losses, first
    gradient (Adam's first moment after the first step over 1 - beta1)
    and change are compared with it, and the B the program had put into
    each recorded slot must be zero. With ``ctx.control`` the reference in
    that lower precision stands in the program's place as well, and is
    held to the same limits (``Run.check_control``)."""
    tr, spec = ctx.traffic, ctx.spec
    decoder = harness.reference(spec)
    beta1 = tr["adamw"]["beta1"]
    prog, ref, ctrl = [], [], []
    for rec in records:
        init = weights.adapters(spec, [r for _, r in rec.jobs], ctx.seed,
                                rec.stream)
        init = {t: {m: np.asarray(v) for m, v in ab.items()}
                for t, ab in init.items()}
        for i, (z, (job, rank)) in enumerate(zip(rec.slots, rec.jobs)):
            label = f"{rec.stream}:{z}"
            hp = dict(tr["adamw"], lr=jobs[job].learning_rate, rank=rank)
            a0 = weights.slot(init, i)
            rows = [{"tokens": b["tokens"][i], "labels": b["labels"][i]}
                    for b in rec.batches]
            r_losses, r_g1, r_after = decoder.train(spec, params, a0, rows,
                                                    hp)
            ref.append(side(label, r_losses, r_g1, r_after, a0))
            g1 = {t: {m: ab[m][:, i] / (1 - beta1) for m in ab}
                  for t, ab in rec.mu1.items()}
            prog.append(side(label, [x[i] for x in rec.losses], g1,
                             weights.slot(rec.after, i), a0))
            if ctx.control:
                c_losses, c_g1, c_after = decoder.train(
                    spec, params, a0, rows, hp, quant=ctx.control)
                ctrl.append(side(label, c_losses, c_g1, c_after, a0))
    ref = merge(ref)
    if ctx.detail:          # bench/calibrate.py keeps every value read
        sides = [("prog", merge(prog)), ("ref", ref)] + (
            [("ctrl", merge(ctrl))] if ctx.control else [])
        run.counters["detail"] = {
            name: {k: {str(kk): vv for kk, vv in part[k].items()}
                   for k in part}
            for name, part in sides}
    got = readings(merge(prog), ref)
    run.counters["leaves_counted"] = got.pop("leaves_counted")
    run.counters["leaves"] = got.pop("leaves")
    got["init_b_abs"] = max(rec.b_abs for rec in records)
    for name, value in got.items():
        run.check(name, value, ctx.limits[name])
    if ctx.control:
        got = readings(merge(ctrl), ref)
        del got["leaves_counted"], got["leaves"]
        got["init_b_abs"] = 0.0         # the control starts from ours
        for name, value in got.items():
            run.check_control(name, value, ctx.limits[name])
