"""Durable crash recovery (paper §4 service hardening): write-ahead event
journal round-trips, in-flight SlotSnapshot checkpoints with bitwise
resume, chaos fault injection (elastic <= static survives it), and
graceful degradation on corrupt durable state."""
import glob
import json
import os

import numpy as np
import pytest
from hypothesis import given, settings
from hypothesis import strategies as st

from repro.checkpoint.checkpoint import load_state_tree, save_state_tree
from repro.checkpoint.taskstate import (SimulatedCrash, TaskCheckpointer,
                                        load_task_checkpoint)
from repro.core.early_exit import EarlyExitConfig
from repro.core.service import TuningService
from repro.sched.chaos import Fault, FaultPlan, FaultyTaskDriver, chaos_spec
from repro.sched.cluster import (ElasticClusterRuntime, SimulatedTaskDriver,
                                 execute_static, sim_task_spec)
from repro.sched.events import (EventKind, ProgressEvent, event_from_json,
                                event_to_json)
from repro.sched.journal import EventJournal, replay_journal
from repro.sched.inter_task import solve

CHUNK_STEPS = 5      # SimulatedTaskDriver default


# ---------------------------------------------------------------------------
# journal: append / rotate / replay
# ---------------------------------------------------------------------------

def test_journal_roundtrip_with_rotation(tmp_path):
    sd = str(tmp_path / "state")
    j = EventJournal(sd, rotate_every=3)
    recs = [{"rec": "session", "total_gpus": 4},
            {"rec": "submit", "name": "a", "kind": "driver",
             "spec": {"name": "a", "duration": 1.0, "gpus": 1,
                      "release": 0.0}},
            {"rec": "submit", "name": "b", "kind": "driver",
             "spec": {"name": "b", "duration": 2.0, "gpus": 2,
                      "release": 0.0}},
            {"rec": "ckpt", "task": "a", "path": "/x/1.npz", "chunk": 1,
             "remaining_steps_bound": 10},
            {"rec": "ckpt", "task": "a", "path": "/x/2.npz", "chunk": 2,
             "remaining_steps_bound": 5},
            {"rec": "event", "event": event_to_json(ProgressEvent(
                kind=EventKind.TASK_COMPLETED, task="b", time=3.0))},
            {"rec": "serve", "task": "b", "path": "/s/b.npz"}]
    for r in recs:
        j.append(r)
    j.close()
    # rotation sealed full segments; the tail stays in current.jsonl
    assert len(glob.glob(os.path.join(sd, "journal",
                                      "segment-*.jsonl"))) == 2
    rep = replay_journal(sd)
    assert not rep.corrupt and not rep.torn_tail
    assert rep.session()["total_gpus"] == 4
    assert sorted(r["name"] for r in rep.submits()) == ["a", "b"]
    assert rep.terminal_tasks() == {"b"}
    assert rep.checkpoints()["a"]["chunk"] == 2      # latest wins
    assert rep.serves() == {"b": "/s/b.npz"}

    # a new journal over the same dir keeps appending, not clobbering
    j2 = EventJournal(sd, rotate_every=3)
    j2.append({"rec": "event", "event": event_to_json(ProgressEvent(
        kind=EventKind.TASK_CANCELLED, task="a", time=4.0))})
    j2.close()
    assert replay_journal(sd).terminal_tasks() == {"a", "b"}


def test_journal_torn_tail_tolerated(tmp_path):
    sd = str(tmp_path / "state")
    j = EventJournal(sd)
    j.append({"rec": "submit", "name": "a", "kind": "driver", "spec": {}})
    j.append({"rec": "submit", "name": "b", "kind": "driver", "spec": {}})
    j.close()
    cur = os.path.join(sd, "journal", "current.jsonl")
    with open(cur, "a") as f:
        f.write('{"rec": "submit", "name": "c"')   # crash mid-append
    rep = replay_journal(sd)
    # a torn final line is the expected crash signature, not corruption
    assert rep.torn_tail and not rep.corrupt
    assert sorted(r["name"] for r in rep.submits()) == ["a", "b"]


def test_journal_corrupt_segment_flagged(tmp_path):
    sd = str(tmp_path / "state")
    j = EventJournal(sd)
    for n in ("a", "b", "c"):
        j.append({"rec": "submit", "name": n, "kind": "driver", "spec": {}})
    j.close()
    cur = os.path.join(sd, "journal", "current.jsonl")
    lines = open(cur).read().splitlines()
    lines[1] = lines[1][: len(lines[1]) // 2]       # mid-file truncation
    with open(cur, "w") as f:
        f.write("\n".join(lines) + "\n")
    rep = replay_journal(sd)
    assert rep.corrupt                               # not a torn tail
    assert "a" in {r["name"] for r in rep.submits()}  # prefix still usable


def test_event_json_roundtrip():
    e = ProgressEvent(kind=EventKind.POD_KILLED, task="t0", time=1.5,
                      job="t0/j", reason="injected", step=7,
                      dropped=("a", "b"), detail="backoff=0.3")
    d = json.loads(json.dumps(event_to_json(e)))
    assert event_from_json(d) == e


def test_state_tree_roundtrip(tmp_path):
    path = str(tmp_path / "st.npz")
    tree = {"snap": {"task/a": {"A": np.arange(6, dtype=np.float32),
                                "B": np.ones((2, 3), np.int64)}},
            "prng": np.asarray([1, 2], np.uint32)}
    meta = {"chunk": 3, "queue": ["x", "y"]}
    save_state_tree(path, tree, meta=meta)
    tree2, meta2 = load_state_tree(path)
    assert meta2["chunk"] == 3 and meta2["queue"] == ["x", "y"]
    assert list(tree2) == list(tree)                 # order preserved
    np.testing.assert_array_equal(tree2["snap"]["task/a"]["A"],
                                  tree["snap"]["task/a"]["A"])
    np.testing.assert_array_equal(tree2["snap"]["task/a"]["B"],
                                  tree["snap"]["task/a"]["B"])
    np.testing.assert_array_equal(tree2["prng"], tree["prng"])


# ---------------------------------------------------------------------------
# kill-and-recover end to end on the real tiny engine
# ---------------------------------------------------------------------------

@pytest.fixture(scope="module")
def tiny_env():
    from repro.data.synthetic import make_task_dataset
    from tests.conftest import reduced_f32
    cfg = reduced_f32("paper-llama-tiny", num_layers=2, d_model=128,
                      vocab=256)
    ds = make_task_dataset("rec", cfg.vocab_size, seq_len=32, num_train=64,
                           num_val=16, difficulty=0.2)
    return cfg, ds


EE = EarlyExitConfig(warmup_ratio=0.2, select_ratio=0.5)


def _mk_task(tiny_env):
    """Ragged widths (batch_size 2 vs 4), mixed TRUE ranks (4 vs 8), and
    more jobs than slots — so the crash lands mid-rotation with live
    PRNG streams and per-slot hyperparameters to restore."""
    from repro.core import engine as alto
    cfg, ds = tiny_env
    return alto.Task(model=cfg, dataset=ds, num_gpus=2, max_steps=10,
                     num_slots=2, name="tenant-r",
                     search_space={"lr": [1e-3, 3e-3], "rank": [4, 8],
                                   "batch_size": [2, 4]})


@pytest.fixture(scope="module")
def baseline(tiny_env):
    """Uninterrupted reference run, shared across the recovery tests."""
    svc = TuningService(total_gpus=4, eval_every=2)
    res = svc.submit(_mk_task(tiny_env), early_exit=EE).result()
    return res, svc._meta["tenant-r"].driver._steps


def _crash_run(tiny_env, tmp_path, fail_after):
    sd = str(tmp_path / "state")
    svc = TuningService(total_gpus=4, eval_every=2, state_dir=sd,
                        ckpt_every=1)
    svc._ckpt.fail_after["*"] = fail_after
    h = svc.submit(_mk_task(tiny_env), early_exit=EE)
    with pytest.raises(SimulatedCrash):
        h.result()
    return sd


def test_kill_and_recover_bitwise(tiny_env, baseline, tmp_path):
    res0, steps0 = baseline
    sd = _crash_run(tiny_env, tmp_path, fail_after=3)
    svc = TuningService.recover(sd, tasks=[(_mk_task(tiny_env), EE)])
    rep = svc.run_until_idle()
    res = rep.task_results["tenant-r"]
    # bitwise: same winner, bit-identical best validation loss
    assert res.best_job == res0.best_job
    assert float(res.best_val) == float(res0.best_val)
    # the resumed run recomputed strictly less than a from-zero restart
    assert svc._meta["tenant-r"].driver._steps < steps0
    recov = [e for e in rep.events if e.kind is EventKind.TASK_RECOVERED]
    assert len(recov) == 1 and recov[0].reason == "resumed"


def test_corrupt_checkpoint_degrades_to_requeue(tiny_env, baseline,
                                                tmp_path):
    res0, steps0 = baseline
    sd = _crash_run(tiny_env, tmp_path, fail_after=2)
    for p in glob.glob(os.path.join(sd, "ckpt", "*", "*.npz")):
        with open(p, "wb") as f:
            f.write(b"\x00" * 100)                   # trash every snapshot
    svc = TuningService.recover(sd, tasks=[(_mk_task(tiny_env), EE)])
    rep = svc.run_until_idle()
    res = rep.task_results["tenant-r"]
    # degraded but correct: full re-run from step 0, same final answer
    assert res.best_job == res0.best_job
    assert float(res.best_val) == float(res0.best_val)
    assert svc._meta["tenant-r"].driver._steps == steps0
    recov = [e for e in rep.events if e.kind is EventKind.TASK_RECOVERED]
    assert len(recov) == 1 and recov[0].reason == "requeued"


def test_corrupt_journal_distrusts_snapshots(tiny_env, baseline, tmp_path):
    res0, steps0 = baseline
    sd = _crash_run(tiny_env, tmp_path, fail_after=2)
    cur = os.path.join(sd, "journal", "current.jsonl")
    lines = open(cur).read().splitlines()
    assert len(lines) > 3
    lines[2] = "{garbage"                            # mid-file corruption
    with open(cur, "w") as f:
        f.write("\n".join(lines) + "\n")
    svc = TuningService.recover(sd, tasks=[(_mk_task(tiny_env), EE)])
    rep = svc.run_until_idle()
    res = rep.task_results["tenant-r"]
    assert res.best_job == res0.best_job
    assert float(res.best_val) == float(res0.best_val)
    assert svc._meta["tenant-r"].driver._steps == steps0   # from zero


def test_checkpointer_prunes_and_latest(tmp_path):
    ck = TaskCheckpointer(str(tmp_path / "s"), every=1, keep=2)
    tdir = os.path.join(ck.dir, "t")
    os.makedirs(tdir)
    for i in (1, 2, 3):
        save_state_tree(os.path.join(tdir, f"chunk-{i:06d}.npz"),
                        {"x": np.zeros(1)}, meta={"chunk": i, "schema": 1})
        ck._prune(tdir)
    left = sorted(os.listdir(tdir))
    assert left == ["chunk-000002.npz", "chunk-000003.npz"]
    assert ck.latest("t").endswith("chunk-000003.npz")
    assert load_task_checkpoint(ck.latest("t"))[1]["chunk"] == 3
    # unreadable artifact -> None, never an exception
    with open(ck.latest("t"), "wb") as f:
        f.write(b"nope")
    assert load_task_checkpoint(ck.latest("t")) is None


# ---------------------------------------------------------------------------
# chaos harness
# ---------------------------------------------------------------------------

def _chaos_workload(rng, G, plan_faults):
    tasks = []
    n = int(rng.integers(2, 6))
    for i in range(n):
        name = f"t{i}"
        K = int(rng.integers(2, 16))
        Z = int(rng.integers(1, 5))
        total = int(rng.integers(10, 120))
        warm = int(rng.integers(1, max(total // 4, 2)))
        step_time = float(rng.uniform(0.005, 0.05))
        gpus = int(rng.integers(1, G + 1))
        chunk_bound = CHUNK_STEPS * step_time
        work = total * step_time
        if rng.random() < 0.7:
            faults = tuple(
                Fault(at_progress=float(rng.uniform(0.0, work)),
                      backoff=float(rng.uniform(0.0, 0.5)))
                for _ in range(int(rng.integers(1, 4))))
            plan_faults.faults[name] = faults
        faults = plan_faults.for_task(name)
        spec = chaos_spec(
            sim_task_spec(name, K=K, Z=Z, total_steps=total,
                          warmup_steps=warm, step_time_s=step_time,
                          gpus=gpus),
            faults, chunk_bound)

        def factory(name=name, K=K, Z=Z, total=total, warm=warm,
                    step_time=step_time, faults=faults, cb=chunk_bound):
            inner = SimulatedTaskDriver(name, K=K, Z=Z, total_steps=total,
                                        warmup_steps=warm,
                                        step_time_s=step_time)
            return FaultyTaskDriver(name, inner, faults, cb)
        tasks.append((spec, factory))
    return tasks


@settings(deadline=None, max_examples=15, derandomize=True)
@given(seed=st.integers(0, 10_000), G=st.sampled_from([2, 4, 8]))
def test_chaos_elastic_le_static(seed, G):
    """Elastic <= static survives fault injection: both sides wrap the
    SAME deterministic fault plans (faults fire on task-local progress,
    so penalties are schedule-independent) and both plan with the same
    per-fault reserve."""
    rng = np.random.default_rng(seed)
    plan_faults = FaultPlan(faults={})
    tasks = _chaos_workload(rng, G, plan_faults)
    specs = [s for s, _ in tasks]
    plan = solve(specs, G, "cp")
    static = execute_static(plan, G, {s.name: f for s, f in tasks})
    rt = ElasticClusterRuntime(G)
    for s, f in tasks:
        rt.submit(s, f)
    elastic = rt.run(initial=plan)
    assert elastic.makespan <= static.makespan + 1e-9
    injected = sum(1 for e in elastic.events
                   if e.kind is EventKind.REPLICA_FAILED)
    assert injected == plan_faults.total()
    assert set(elastic.results) == {s.name for s, _ in tasks}


def test_chaos_faulted_loss_identical():
    """Fault injection only costs time: the wrapped driver's result is
    bitwise identical to an un-faulted run of the same task."""
    def clean():
        return SimulatedTaskDriver("t", K=6, Z=3, total_steps=40,
                                   warmup_steps=4, step_time_s=0.02)
    base = clean()
    base.start(0.0)
    while not base.step_chunk().done:
        pass
    faulty = FaultyTaskDriver("t", clean(),
                              [Fault(0.2, 0.1), Fault(0.5, 0.3)], 0.1)
    faulty.start(0.0)
    wall = 0.0
    while True:
        ch = faulty.step_chunk()
        wall += ch.dt
        if ch.done:
            break
    assert faulty.faults_injected == 2
    assert wall > 40 * 0.02                      # retries were billed
    assert faulty.result() == base.result()


def test_pod_kill_requeues_and_completes():
    G = 4
    defs = [dict(K=8, Z=4, total=60, warm=4, step_time=0.02, gpus=2),
            dict(K=6, Z=2, total=40, warm=3, step_time=0.03, gpus=1),
            dict(K=12, Z=4, total=80, warm=5, step_time=0.01, gpus=4)]

    def build():
        rt = ElasticClusterRuntime(G)
        for i, kw in enumerate(defs):
            name = f"t{i}"
            spec = sim_task_spec(name, K=kw["K"], Z=kw["Z"],
                                 total_steps=kw["total"],
                                 warmup_steps=kw["warm"],
                                 step_time_s=kw["step_time"],
                                 gpus=kw["gpus"])

            def factory(name=name, kw=kw):
                return SimulatedTaskDriver(
                    name, K=kw["K"], Z=kw["Z"], total_steps=kw["total"],
                    warmup_steps=kw["warm"], step_time_s=kw["step_time"])
            rt.submit(spec, factory)
        return rt

    rt0 = build()
    base = rt0.run()
    rt = build()
    rt.begin()
    start, end = base.task_starts["t0"], base.task_ends["t0"]
    backoff = 0.3
    rt.inject_fault("t0", at=start + 0.5 * (end - start), backoff=backoff)
    while rt.step():
        pass
    rep = rt.report()
    kills = [e for e in rep.events if e.kind is EventKind.POD_KILLED]
    assert len(kills) == 1 and rep.pod_kills == 1
    assert set(rep.results) == {"t0", "t1", "t2"}    # everyone finished
    resumed = [e for e in rep.events
               if e.kind is EventKind.TASK_STARTED and e.task == "t0"]
    assert len(resumed) == 2                         # killed, then resumed
    # bounded degradation: at most the backoff plus a few atomic chunks
    # of replan slack on top of the fault-free makespan
    chunk = CHUNK_STEPS * max(kw["step_time"] for kw in defs)
    assert rep.makespan <= base.makespan + backoff + 3 * chunk + 1e-9


# ---------------------------------------------------------------------------
# wall-clock driver + hardening satellites
# ---------------------------------------------------------------------------

def test_run_forever_drains_submissions():
    import time as _time
    svc = TuningService(total_gpus=4)
    loop = svc.run_forever(poll_s=0.01)
    try:
        spec = sim_task_spec("w0", K=4, Z=2, total_steps=20,
                             warmup_steps=2, step_time_s=0.01, gpus=2)

        def factory():
            return SimulatedTaskDriver("w0", K=4, Z=2, total_steps=20,
                                       warmup_steps=2, step_time_s=0.01)
        h = svc.submit_spec(spec, factory)
        deadline = _time.monotonic() + 30.0
        while (not h.status().state.terminal
               and _time.monotonic() < deadline):
            _time.sleep(0.01)
        assert h.status().state.terminal
        assert "w0" in svc._results()
    finally:
        loop.stop()
    assert not loop.alive


def test_run_forever_stop_reraises_pump_crash():
    """A crash inside the pump ends the loop, and ``stop()`` hands the
    exception to the caller instead of leaving it in a log line."""
    import time as _time
    svc = TuningService(total_gpus=4)
    loop = svc.run_forever(poll_s=0.01)
    spec = sim_task_spec("boom", K=4, Z=2, total_steps=20, warmup_steps=2,
                         step_time_s=0.01, gpus=2)

    def factory():
        raise RuntimeError("driver failed to start")
    svc.submit_spec(spec, factory)
    deadline = _time.monotonic() + 30.0
    while loop.alive and _time.monotonic() < deadline:
        _time.sleep(0.01)
    assert not loop.alive
    with pytest.raises(RuntimeError, match="driver failed to start"):
        loop.stop()


def test_profile_store_corrupt_file_falls_back(tmp_path):
    from repro.sched.profiler import ProfileStore
    p = str(tmp_path / "prof.json")
    with open(p, "w") as f:
        f.write('{"version": 1, "entries": [tr')
    store = ProfileStore.load(p)                     # warns, never raises
    assert store.observations(("x", 1)) == 0


def test_publish_checkpoint_corrupt_artifact(tmp_path):
    from repro.serve.pool import AdapterPool, CorruptCheckpoint
    from tests.conftest import reduced_f32
    cfg = reduced_f32("paper-llama-tiny", num_layers=2, d_model=64,
                      vocab=64)
    pool = AdapterPool(cfg, Z=2)
    bad = str(tmp_path / "bad.npz")
    with open(bad, "wb") as f:
        f.write(b"\x00" * 64)
    with pytest.raises(CorruptCheckpoint):
        pool.publish_checkpoint(bad)
    assert pool.free_slots() == [0, 1]               # pool untouched
