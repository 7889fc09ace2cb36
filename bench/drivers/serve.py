"""Serving driver: an open-loop client of ``ServingFrontend`` in continuous
mode over an ``AdapterPool`` whose adapters were put in place by
``publish``.

Set-up makes the backbone and the adapters from the seed, publishes the
adapters, and serves one warm-up request per power-of-two prompt bucket the
mix can produce, so every join-and-decode and decode program is compiled
(or loaded) before the window.

The window sends each request at its due time (``serve.submit``), steps
the frontend while any request is queued or in a lane (``serve.step``) and
otherwise sleeps until the next is due (``serve.wait``). Times are taken on
the client side: a request's time to first token runs from its due time to
the return of the step that produced its first token, and the gaps
between tokens are read after every step. After the window closes the
loop serves on until every request due in it is done, up to
``drain_s``; one that is not done by then has failed.

Then the program's state is freed and the float32 reference runs once
over a sample of the finished requests (the one with most served tokens
among them), each prompt with its served tokens.
"""
from __future__ import annotations

import time
from typing import Dict, List, Optional

import numpy as np

from bench import harness, weights, workload
from bench.harness import BenchError, Run


# edges of the inter-token gap histogram the counters keep (ms)
ITL_BINS_MS = [0, 40, 60, 80, 100, 120, 140, 150, 160, 170, 180, 200, 250,
               300, 400, 1e9]


def buckets(traffic: Dict) -> List[int]:
    """The padded prompt lengths a join can take: powers of two from the
    shortest prompt's to the longest's, capped at ``max_len``."""
    lo, hi = traffic["prompt"]["min"], traffic["prompt"]["max"]
    out, p = [], 1 << (lo - 1).bit_length()
    while True:
        out.append(min(p, traffic["max_len"]))
        if p >= hi:
            return out
        p *= 2


def percentile(values: List[float], q: float) -> float:
    return float(np.percentile(np.asarray(values, np.float64), q))


def run(ctx: harness.Context, t_start: float) -> Run:
    import jax
    from repro.models import model as M
    from repro.serve.frontend import ServingFrontend
    from repro.serve.pool import AdapterPool
    from repro.serve.replica import ServingReplica

    tr, spec, cfg = ctx.traffic, ctx.spec, ctx.cfg
    run = Run()
    params = weights.make_params(spec, ctx.seed)
    weights.check_layout(params, jax.eval_shape(
        lambda: M.init_params(jax.random.PRNGKey(0), cfg)))
    ranks = tr["adapter_ranks"]
    ads = weights.adapters(spec, ranks, ctx.seed)
    pool = AdapterPool(cfg, Z=len(ranks))
    replica = ServingReplica(cfg, params, pool, lanes=tr["lanes"],
                             max_len=tr["max_len"])
    front = ServingFrontend(replica, mode="continuous")
    names = [f"adapter{i}" for i in range(len(ranks))]
    for i, (name, r) in enumerate(zip(names, ranks)):
        front.publish(name, weights.slot(ads, i), r)
    del ads
    g = workload.rng(ctx.seed, 3)
    for P in buckets(tr):
        front.submit(names[0], g.integers(0, cfg.vocab_size, P), 3)
        front.drain()
    schedule = workload.serve_schedule(tr, cfg.vocab_size, ctx.seed,
                                       ctx.seconds)

    live: Dict[str, object] = {}            # request id -> ServeRequest
    reqs: List[Dict] = []
    for s in schedule:
        reqs.append({"spec": s, "obj": None, "seen": 0, "first": None,
                     "last": None, "gaps": []})
    i = 0
    backlog = None
    late: List[float] = []      # how late the generator sent each request
    with ctx.window(run):
        t0 = harness.clock()
        run.e2e["setup_s"] = t0 - t_start
        close = t0 + ctx.seconds
        drain_end = close + tr["drain_s"]
        while True:
            now = harness.clock()
            if backlog is None and now >= close:
                backlog = sum(1 for r in reqs[:i]
                              if r["obj"] is None or not r["obj"].done)
            if i < len(reqs) and t0 + reqs[i]["spec"].due_s <= now:
                with harness.annotate("serve.submit"):
                    while i < len(reqs) and \
                            t0 + reqs[i]["spec"].due_s <= now:
                        s = reqs[i]["spec"]
                        late.append(now - (t0 + s.due_s))
                        rid = front.submit(names[s.adapter], s.prompt,
                                           s.max_new)
                        obj = front._queues[names[s.adapter]][-1]
                        assert obj.request_id == rid
                        reqs[i]["obj"] = obj
                        live[rid] = reqs[i]
                        i += 1
            if live:
                with harness.annotate("serve.step"):
                    front.step_continuous()
                now = harness.clock()
                for rid, r in list(live.items()):
                    n = len(r["obj"].tokens)
                    if n > r["seen"]:
                        if r["first"] is None:
                            r["first"] = now
                        else:
                            r["gaps"].append(now - r["last"])
                        r["gaps"].extend([0.0] * (n - r["seen"] - 1))
                        r["last"] = now
                        r["seen"] = n
                    if r["obj"].done:
                        del live[rid]
            elif i < len(reqs):
                with harness.annotate("serve.wait"):
                    time.sleep(max(t0 + reqs[i]["spec"].due_s
                                   - harness.clock(), 0.0))
            if i == len(reqs) and not live:
                break
            if harness.clock() >= drain_end:
                break
        t1 = harness.clock()
    if backlog is None:
        backlog = 0
    ctx.read_peak(run)

    ttft, itl, failed = [], [], 0
    for r in reqs:
        due = t0 + r["spec"].due_s
        done = r["obj"] is not None and r["obj"].done
        failed += not done
        ttft.append((r["first"] if r["first"] is not None else t1) - due)
        itl.extend(r["gaps"])
    run.e2e["itl_p95_ms"] = 1e3 * percentile(itl, 95) if itl else 0.0
    run.attempted, run.failed = len(reqs), failed
    run.counters.update(
        window_s=t1 - t0, requests=len(reqs), backlog_at_close=backlog,
        lanes=tr["lanes"] * len(ranks), ttft_p50_ms=1e3 * percentile(ttft, 50),
        ttft_p90_ms=1e3 * percentile(ttft, 90),
        itl_p50_ms=1e3 * percentile(itl, 50) if itl else 0.0,
        itl_p90_ms=1e3 * percentile(itl, 90) if itl else 0.0,
        itl_p99_ms=1e3 * percentile(itl, 99) if itl else 0.0,
        itl_hist_ms=np.histogram(1e3 * np.asarray(itl or [0.0]),
                                 bins=ITL_BINS_MS)[0].tolist(),
        decode_steps=replica.total_decode_steps, joins=replica.joins,
        block_prefills=replica.block_prefills,
        served_tokens=sum(r["seen"] for r in reqs), chips=ctx.chips,
        submit_late_p95_ms=1e3 * percentile(late, 95) if late else 0.0,
        submit_late_max_ms=1e3 * max(late, default=0.0))

    finished = [(r["spec"], list(r["obj"].tokens)) for r in reqs
                if r["obj"] is not None and r["obj"].done]
    del front, replica, pool, live
    for r in reqs:
        r["obj"] = None
    harness.free()
    t_ref = harness.clock()
    compare(ctx, run, params, finished)
    run.counters["reference_s"] = harness.clock() - t_ref
    return run


def sample(finished: List, traffic: Dict, seed: int) -> List:
    """The request with most served tokens, then others drawn from the
    seed, until ``check_tokens`` served tokens or ``check_requests``
    requests."""
    if not finished:
        return []
    order = sorted(range(len(finished)), key=lambda j: -len(finished[j][1]))
    rest = list(workload.rng(seed, 4).permutation(order[1:]))
    picked, tokens = [order[0]], len(finished[order[0]][1])
    for j in rest:
        if tokens >= traffic["check_tokens"] or \
                len(picked) >= traffic["check_requests"]:
            break
        picked.append(int(j))
        tokens += len(finished[j][1])
    return [finished[j] for j in picked]


def gaps_for(ctx, params, picked: List, control: Optional[str] = None):
    """Per request: the reference's gaps of the served tokens (and, with
    ``control``, of the first choices of the reference in that
    precision)."""
    import jax
    import jax.numpy as jnp
    spec, tr = ctx.spec, ctx.traffic
    decoder = harness.reference(spec)
    ads = weights.adapters(spec, tr["adapter_ranks"], ctx.seed)
    fn = jax.jit(lambda p, a, s, t: decoder.served_gaps(spec, p, a, s, t,
                                                        control))
    out = []
    L = tr["max_len"]
    for s, toks in picked:
        P = len(s.prompt)
        seq = np.zeros((1, L), np.int32)
        seq[0, :P] = s.prompt
        seq[0, P:P + len(toks) - 1] = toks[:-1]
        tgt = np.full((L,), -1, np.int32)
        tgt[P - 1:P - 1 + len(toks)] = toks
        served, ctrl = fn(params, weights.slot(ads, s.adapter),
                          jnp.asarray(seq), jnp.asarray(tgt))
        out.append((np.asarray(served),
                    None if ctrl is None else np.asarray(ctrl)))
    return out


def compare(ctx, run: Run, params, finished: List) -> None:
    """The widest gap by which a served token's logit lies below the
    reference's best, over the sampled requests. With ``ctx.control`` the
    first choices of the reference in that lower precision are read the
    same way and held to the same limit (the control)."""
    picked = sample(finished, ctx.traffic, ctx.seed)
    if not picked:
        raise BenchError("no request finished")
    gaps = gaps_for(ctx, params, picked, control=ctx.control)
    run.counters["checked_requests"] = len(picked)
    run.counters["checked_tokens"] = sum(len(t) for _, t in picked)
    if ctx.control:
        run.check_control("served_logit_gap",
                          max(float(c.max()) for _, c in gaps),
                          ctx.limits["served_logit_gap"])
    if ctx.detail:              # bench/calibrate.py keeps these
        run.counters["detail"] = {
            "served": [float(g.max()) for g, _ in gaps],
            "served_flips": [int((g > 0).sum()) for g, _ in gaps],
            "tokens": [len(t) for _, t in picked]}
        if ctx.control:
            run.counters["detail"].update(
                ctrl=[float(c.max()) for _, c in gaps],
                ctrl_flips=[int((c > 0).sum()) for _, c in gaps])
    run.check("served_logit_gap", max(float(g.max()) for g, _ in gaps),
              ctx.limits["served_logit_gap"])
