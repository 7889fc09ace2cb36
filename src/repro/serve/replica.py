"""ServingReplica: continuous-batching decode over one adapter pool.

One frozen backbone serves every resident adapter of an ``AdapterPool``
at once: in-flight requests map to ``(slot, lane)`` coordinates of the
slot-stacked forward — slot = the request's adapter, lane = one of the
replica's ``lanes`` decode streams per slot — so each decode step
advances ``Z x lanes`` streams in a single fused kernel launch. Prefill
and decode both run with the pool's ``ranks`` vector bound via
``LORA.slot_ranks`` (per-slot TRUE ranks, the rank-local grouped-LoRA
path on the Pallas backend; on the jnp backend the full-rank select is
the identity, which keeps fused-vs-solo decode bitwise equal).

Two batching disciplines share the replica:

**Continuous (default drive mode).** The decode cache carries a
PER-LANE position vector (``init_cache(per_lane=True)``: ``pos`` is
``[Z, lanes]``, ring ``k_pos`` is ``[Z, lanes, W]``), so every lane is
its own stream: a request joins the moment a lane in its adapter's slot
frees up — block prefill writes its prompt into its own lane cache at
offsets 0..P-1 (``prefill_lanes``; ring/recurrent families stream the
prompt through the decode step after a lane reset) — and leaves the
moment it has ``max_new`` tokens, freeing the lane for the next
request. The cache is NEVER epoch-reset while any lane is live; idle
lanes are frozen bitwise by the ``active`` mask. Per-request
``RequestRecord`` latency accounting (queue/prefill/decode) replaces
round accounting.

**Round-based (legacy / baseline).** ``serve_round`` keeps the PR-7
behavior — one *global* cache position, so requests only join at a
fresh cache epoch and finished lanes idle (re-feeding their last token)
until the slowest stream drains. It remains the A/B baseline the
continuous mode is benchmarked against (``bench_continuous.py``) and
the greedy bitwise-test path.

Sampling: requests may carry ``temperature``/``top_k`` (continuous mode;
greedy when ``temperature == 0``, the default and the bitwise path).
The sample key is per-lane: ``fold_in(fold_in(PRNGKey(sample_seed),
request.seed), token_index)`` — deterministic under a fixed seed and
independent of WHEN the request joined or which lane it landed on.

Hot ``publish``/``retire`` on the pool between decode steps is sound in
both modes — slot isolation — and is exactly what the serving isolation
tests pin down.

The host work around each launch is marked with ``TraceAnnotation``
spans named ``serve.*``, on the profiler's clock; their counts are
numbers the host already holds, so a span never waits on the device.
"""
from __future__ import annotations

import dataclasses
import time
from typing import Callable, Dict, List, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np
from jax.profiler import TraceAnnotation

from repro.configs.base import ModelConfig
from repro.core import lora as LORA
from repro.core.steps import (make_join_decode_step, make_lane_prefill_step,
                              make_prefill_step, make_serve_step)
from repro.models import model as M
from repro.serve.pool import AdapterPool


@dataclasses.dataclass
class ServeRequest:
    """One decode request routed to a resident adapter."""
    request_id: str
    adapter_id: str
    prompt: np.ndarray            # [P] int32 token ids, P >= 1
    max_new: int
    temperature: float = 0.0      # 0 => greedy (the bitwise path)
    top_k: int = 0                # 0 => full vocab
    seed: int = 0                 # folded into the per-lane sample key
    tokens: List[int] = dataclasses.field(default_factory=list)
    # lane-lifecycle bookkeeping (filled by the replica / frontend)
    fed: int = 0                  # prompt+generated tokens consumed so far
    submit_t: Optional[float] = None
    join_t: Optional[float] = None
    first_token_t: Optional[float] = None
    done_t: Optional[float] = None

    @property
    def done(self) -> bool:
        return len(self.tokens) >= self.max_new


@dataclasses.dataclass
class RequestRecord:
    """Per-request completion record (continuous mode): the latency
    breakdown that replaces round-level accounting."""
    request_id: str
    adapter_id: str
    prompt_len: int
    new_tokens: int
    queue_s: float                # submit -> lane assignment
    prefill_s: float              # lane assignment -> first token
    decode_s: float               # first token -> completion
    total_s: float                # submit -> completion


@dataclasses.dataclass
class RoundStats:
    """One cache epoch's accounting (round-based mode)."""
    requests: int
    generated: int                # tokens produced this round
    decode_steps: int             # fused step invocations (incl. prefill
                                  # steps when streaming token-by-token)
    wall_s: float
    logits: List[Tuple[int, np.ndarray]]   # (position, [Z,lanes,V]) when
                                           # recording is on


class ServingReplica:
    """Lane scheduler over ``pool.Z`` x ``lanes`` decode streams."""

    def __init__(self, cfg: ModelConfig, params, pool: AdapterPool, *,
                 lanes: int = 4, max_len: int = 64, ring: bool = False,
                 sample_seed: int = 0, join_batch: int = 2,
                 join_wait_steps: int = 1):
        assert lanes >= 1 and max_len >= 2
        self.cfg = cfg
        self.params = params
        self.pool = pool
        self.lanes = lanes
        self.max_len = max_len
        self.ring = ring and cfg.family != "ssm"
        # block prefill writes the whole prompt in one forward; ring caches
        # and recurrent families need per-position writes (launch parity)
        self._block_prefill = (not self.ring
                               and cfg.family not in ("ssm", "hybrid"))
        prefill = make_prefill_step(cfg)
        serve = make_serve_step(cfg)
        lane_prefill = make_lane_prefill_step(cfg)
        join_decode = make_join_decode_step(cfg)

        # every wrapper also returns the fused greedy argmax: the hot
        # per-step host sync then transfers [Z, lanes] int32 instead of
        # dispatching a separate argmax program and fetching full logits
        def ranked_prefill(params, lora, cache, batch, ranks):
            with LORA.slot_ranks(ranks):
                logits, cache = prefill(params, lora, cache, batch)
            return logits, jnp.argmax(logits, axis=-1), cache

        def ranked_decode(params, lora, cache, tokens, ranks):
            with LORA.slot_ranks(ranks):
                logits, cache = serve(params, lora, cache, tokens)
            return logits, jnp.argmax(logits, axis=-1), cache

        def ranked_decode_lanes(params, lora, cache, tokens, active, ranks):
            with LORA.slot_ranks(ranks):
                logits, cache = serve(params, lora, cache, tokens, active)
            return logits, jnp.argmax(logits, axis=-1), cache

        def ranked_lane_prefill(params, lora, cache, tokens, mask, plens,
                                ranks):
            with LORA.slot_ranks(ranks):
                logits, cache = lane_prefill(params, lora, cache, tokens,
                                             mask, plens)
            return logits, jnp.argmax(logits, axis=-1), cache

        def ranked_join_decode(params, lora, cache, tokens, mask, plens,
                               cur, active, ranks):
            with LORA.slot_ranks(ranks):
                return join_decode(params, lora, cache, tokens, mask,
                                   plens, cur, active)

        self._prefill = jax.jit(ranked_prefill)
        self._decode = jax.jit(ranked_decode)
        self._decode_lanes = jax.jit(ranked_decode_lanes)
        self._lane_prefill = jax.jit(ranked_lane_prefill)
        self._join_decode = jax.jit(ranked_join_decode)
        self._reset_lanes = jax.jit(
            lambda cache, mask: M.reset_lanes(cfg, cache, mask))
        self._sample_key = jax.random.PRNGKey(sample_seed)
        self.total_generated = 0
        self.total_decode_steps = 0
        self.total_wall_s = 0.0
        self.rounds = 0
        # continuous-mode state: one live per-lane cache, never epoch-reset
        self._cache: Optional[Dict] = None
        self._cur = np.zeros((pool.Z, lanes), np.int32)
        self._active = np.zeros((pool.Z, lanes), bool)
        self._active_dev: Optional[jnp.ndarray] = None   # device mirror
        self._lane_req: Dict[Tuple[int, int], ServeRequest] = {}
        self._pending_joins: Dict[Tuple[int, int], ServeRequest] = {}
        self._join_step: Dict[Tuple[int, int], int] = {}
        # joins flush when >= join_batch are pending, the oldest has
        # waited join_wait_steps fused steps, or no lane is decoding —
        # merging near-simultaneous arrivals into ONE prefill launch
        self.join_batch = max(join_batch, 1)
        self.join_wait_steps = max(join_wait_steps, 0)
        self.joins = 0
        self.block_prefills = 0     # fused ragged prefill launches
        self.records: List[RequestRecord] = []
        self.step_logits: List[Tuple[int, np.ndarray]] = []

    # ------------------------------------------------------------ lanes
    def busy_lanes(self) -> int:
        return len(self._lane_req) + len(self._pending_joins)

    def free_lane(self, slot: int) -> Optional[int]:
        """First free lane in the slot's row, or None."""
        for lane in range(self.lanes):
            c = (slot, lane)
            if c not in self._lane_req and c not in self._pending_joins:
                return lane
        return None

    def try_join(self, r: ServeRequest) -> bool:
        """Assign the request to a free lane of its adapter's slot; it is
        prefixed (block prefill or lane-reset streaming) right before the
        next fused decode step. Returns False when the row is full."""
        assert len(r.prompt) >= 1
        assert len(r.prompt) + r.max_new <= self.max_len, \
            f"request {r.request_id!r} exceeds max_len={self.max_len}"
        slot = self.pool.slot_of(r.adapter_id)
        lane = self.free_lane(slot)
        if lane is None:
            return False
        r.join_t = time.perf_counter()
        if r.submit_t is None:
            r.submit_t = r.join_t
        self._pending_joins[(slot, lane)] = r
        self._join_step[(slot, lane)] = self.total_decode_steps
        self.joins += 1
        return True

    def _ensure_cache(self) -> None:
        if self._cache is None:
            self._cache = M.init_cache(self.cfg, self.pool.Z, self.lanes,
                                       self.max_len, ring=self.ring,
                                       per_lane=True)

    # ------------------------------------------------------------ sampling
    def _sample(self, r: ServeRequest, greedy_tok: int,
                logits_row: Optional[np.ndarray]) -> int:
        if r.temperature <= 0.0:
            return greedy_tok
        key = jax.random.fold_in(
            jax.random.fold_in(self._sample_key, r.seed), len(r.tokens))
        logits = jnp.asarray(logits_row, jnp.float32) / r.temperature
        if r.top_k and r.top_k < logits.shape[-1]:
            kth = jnp.sort(logits)[-r.top_k]
            logits = jnp.where(logits >= kth, logits, -jnp.inf)
        return int(jax.random.categorical(key, logits))

    # ------------------------------------------------------------ joins
    def _flush_joins(self) -> None:
        """Write pending joiners' prompts into their own lane caches.
        Non-ring attention families block-prefill — ONE fused ragged
        ``prefill_lanes`` launch per step, prompts right-padded to the
        next power of two of the longest joiner (bounds compile count;
        the per-lane ``plens`` keeps padded prefill bitwise identical to
        exact-length); ring/recurrent families reset the lane and stream
        the prompt through decode."""
        pending, self._pending_joins = self._pending_joins, {}
        self._join_step.clear()
        if not pending:
            return
        with TraceAnnotation("serve.join"):
            Z, lanes = self.pool.Z, self.lanes
            block: Dict[Tuple[int, int], ServeRequest] = {}
            stream: Dict[Tuple[int, int], ServeRequest] = {}
            for coord, r in pending.items():
                if self._block_prefill and len(r.prompt) > 1:
                    block[coord] = r
                else:
                    stream[coord] = r
            if block:
                P = max(len(r.prompt) for r in block.values())
                P = min(1 << (P - 1).bit_length(),   # pow-2 padding bucket
                        self.max_len)                # (cache cap)
                toks, mask, plens = self._join_batch(block, P)
                _request_spans(block.values())
                logits, greedy, self._cache = self._lane_prefill(
                    self.params, self.pool.lora, self._cache, toks, mask,
                    plens, self.pool.ranks)
                self.block_prefills += 1
                sampled = any(r.temperature > 0 for r in block.values())
                nxt, rows = _fetch(greedy, logits if sampled else None)
                for (s, lane), r in block.items():
                    tok = self._sample(
                        r, int(nxt[s, lane]),
                        None if rows is None else rows[s, lane])
                    r.tokens.append(tok)
                    self.total_generated += 1
                    r.fed = len(r.prompt)
                    r.first_token_t = time.perf_counter()
                    self._cur[s, lane] = tok
                    self._activate(s, lane, r)
            if stream:
                mask = np.zeros((Z, lanes), bool)
                for (s, lane) in stream:
                    mask[s, lane] = True
                _request_spans(stream.values())
                self._cache = self._reset_lanes(self._cache,
                                                jnp.asarray(mask))
                for (s, lane), r in stream.items():
                    r.fed = 0
                    self._cur[s, lane] = r.prompt[0]
                    self._activate(s, lane, r)

    def _join_batch(self, joiners: Dict[Tuple[int, int], ServeRequest],
                    P: int) -> Tuple[jnp.ndarray, jnp.ndarray, jnp.ndarray]:
        """Joiners' prompts right-padded to ``P`` in their own lanes, the
        joining-lane mask and the prompt lengths (1 in idle lanes, which
        read index 0), on the device."""
        Z, lanes = self.pool.Z, self.lanes
        toks = np.zeros((Z, lanes, P), np.int32)
        mask = np.zeros((Z, lanes), bool)
        plens = np.ones((Z, lanes), np.int32)
        for (s, lane), r in joiners.items():
            toks[s, lane, :len(r.prompt)] = r.prompt
            mask[s, lane] = True
            plens[s, lane] = len(r.prompt)
        return jnp.asarray(toks), jnp.asarray(mask), jnp.asarray(plens)

    def _activate(self, slot: int, lane: int, r: ServeRequest) -> None:
        self._lane_req[(slot, lane)] = r
        self._active[slot, lane] = True
        self._active_dev = None

    # ------------------------------------------------------------ decode
    def step_continuous(self, on_step: Optional[Callable[[int], None]] = None,
                        record_logits: bool = False) -> List[ServeRequest]:
        """Flush pending joins, run ONE fused per-lane decode step, and
        return the requests completed by it (their lanes are freed — the
        frontend refills them before the next step). ``on_step(i)`` fires
        before the fused step (hot publish/retire hook, like the round
        path). Completion appends a ``RequestRecord`` to ``records``."""
        t0 = time.perf_counter()
        self._ensure_cache()
        flush_due = bool(self._pending_joins) and (
            not self._lane_req
            or len(self._pending_joins) >= self.join_batch
            or self.total_decode_steps - min(self._join_step.values())
            >= self.join_wait_steps)
        # greedy block-prefillable joiners take the FUSED join+decode
        # program: prefill + first-token argmax + one decode step in a
        # single launch (no host round-trip between prefill and the step
        # consuming the first token); sampled or streaming joiners fall
        # back to the separate flush
        fuse = (flush_due and self._block_prefill
                and all(len(r.prompt) > 1 and r.temperature <= 0.0
                        for r in self._pending_joins.values()))
        if flush_due and not fuse:
            self._flush_joins()
        done: List[ServeRequest] = []
        for coord, r in list(self._lane_req.items()):
            if r.done:                      # block prefill covered max_new=1
                done.append(self._complete(coord, r))
        if fuse:
            with TraceAnnotation("serve.join"):
                joiners, self._pending_joins = self._pending_joins, {}
                self._join_step.clear()
                P = max(len(r.prompt) for r in joiners.values())
                P = min(1 << (P - 1).bit_length(), self.max_len)
                toks, mask, plens = self._join_batch(joiners, P)
            if on_step is not None:
                on_step(self.total_decode_steps)
            if self._active_dev is None:
                self._active_dev = jnp.asarray(self._active)
            _request_spans(joiners.values())
            with TraceAnnotation(
                    "serve.dispatch",
                    active_lanes=len(self._lane_req) + len(joiners),
                    lanes=self._active.size):
                p_greedy, logits, greedy, self._cache = self._join_decode(
                    self.params, self.pool.lora, self._cache, toks, mask,
                    plens, jnp.asarray(self._cur), self._active_dev,
                    self.pool.ranks)
            self.block_prefills += 1
            p_nxt, _ = _fetch(p_greedy)
            now = time.perf_counter()
            with TraceAnnotation("serve.emit"):
                for (s, lane), r in joiners.items():
                    tok = int(p_nxt[s, lane])
                    r.tokens.append(tok)
                    self.total_generated += 1
                    r.fed = len(r.prompt)
                    r.first_token_t = now
                    self._cur[s, lane] = tok
                    self._activate(s, lane, r)
                    if r.done:      # max_new == 1: prefill covered it fully
                        done.append(self._complete((s, lane), r))
        else:
            if not self._lane_req:
                self.total_wall_s += time.perf_counter() - t0
                return done
            if on_step is not None:
                on_step(self.total_decode_steps)
            if self._active_dev is None:  # re-upload only on lane churn
                self._active_dev = jnp.asarray(self._active)
            with TraceAnnotation("serve.dispatch",
                                 active_lanes=len(self._lane_req),
                                 lanes=self._active.size):
                logits, greedy, self._cache = self._decode_lanes(
                    self.params, self.pool.lora, self._cache,
                    jnp.asarray(self._cur), self._active_dev,
                    self.pool.ranks)
        keep = record_logits or any(r.temperature > 0
                                    for r in self._lane_req.values())
        nxt, rows = _fetch(greedy, logits if keep else None)
        if record_logits:
            self.step_logits.append((self.total_decode_steps, rows))
        generated = 0
        with TraceAnnotation("serve.emit"):
            for (s, lane), r in list(self._lane_req.items()):
                P = len(r.prompt)
                r.fed += 1
                if r.fed < P:                   # still consuming its prompt
                    self._cur[s, lane] = r.prompt[r.fed]
                    continue
                tok = self._sample(r, int(nxt[s, lane]),
                                   None if rows is None else rows[s, lane])
                if r.first_token_t is None:
                    r.first_token_t = time.perf_counter()
                r.tokens.append(tok)
                generated += 1
                self._cur[s, lane] = tok
                if r.done:
                    done.append(self._complete((s, lane), r))
        self.total_decode_steps += 1
        self.total_generated += generated
        self.total_wall_s += time.perf_counter() - t0
        return done

    def _complete(self, coord: Tuple[int, int],
                  r: ServeRequest) -> ServeRequest:
        r.done_t = time.perf_counter()
        del self._lane_req[coord]
        self._active[coord] = False
        self._active_dev = None
        self.records.append(RequestRecord(
            request_id=r.request_id, adapter_id=r.adapter_id,
            prompt_len=len(r.prompt), new_tokens=len(r.tokens),
            queue_s=r.join_t - r.submit_t,
            prefill_s=r.first_token_t - r.join_t,
            decode_s=r.done_t - r.first_token_t,
            total_s=r.done_t - r.submit_t))
        return r

    # ------------------------------------------------------------ rounds
    def pack(self, requests: List[ServeRequest]
             ) -> Dict[Tuple[int, int], ServeRequest]:
        """Assign requests to (slot, lane); every adapter must be resident
        and get at most ``lanes`` requests in one round."""
        lane_req: Dict[Tuple[int, int], ServeRequest] = {}
        used: Dict[int, int] = {}
        for r in requests:
            s = self.pool.slot_of(r.adapter_id)
            lane = used.get(s, 0)
            assert lane < self.lanes, \
                f"adapter {r.adapter_id!r}: > {self.lanes} requests/round"
            assert len(r.prompt) >= 1
            assert len(r.prompt) + r.max_new <= self.max_len, \
                f"request {r.request_id!r} exceeds max_len={self.max_len}"
            used[s] = lane + 1
            lane_req[(s, lane)] = r
        return lane_req

    def serve_round(self, requests: List[ServeRequest],
                    on_step: Optional[Callable[[int], None]] = None,
                    record_logits: bool = False) -> RoundStats:
        """Drive one cache epoch (round-based baseline): streamed prefill
        + greedy decode until every request has ``max_new`` tokens.
        ``on_step(i)`` fires before the i-th fused step — a hook may hot
        publish/retire adapters on the pool there (visible next step,
        resident slots untouched)."""
        assert requests, "empty round"
        lane_req = self.pack(requests)
        pool = self.pool
        Z, b = pool.Z, self.lanes
        cache = M.init_cache(self.cfg, Z, b, self.max_len, ring=self.ring)
        cur = np.zeros((Z, b), np.int32)
        lens = {len(r.prompt) for r in lane_req.values()}
        logits = None
        logits_log: List[Tuple[int, np.ndarray]] = []
        steps = 0
        t0 = time.perf_counter()
        if self._block_prefill and len(lens) == 1 and min(lens) > 1:
            P0 = lens.pop()
            prompts = np.zeros((Z, b, P0), np.int32)
            for (s, lane), r in lane_req.items():
                prompts[s, lane] = r.prompt
            logits, greedy, cache = self._prefill(
                self.params, pool.lora, cache,
                {"tokens": jnp.asarray(prompts)}, pool.ranks)
            t = P0 - 1                 # logits for position P0-1 in hand
        else:
            for (s, lane), r in lane_req.items():
                cur[s, lane] = r.prompt[0]
            t = -1                     # nothing consumed yet
        generated = 0
        while True:
            if logits is not None:
                nxt, rows = _fetch(greedy, logits if record_logits else None)
                if record_logits:
                    logits_log.append((t, rows))
                with TraceAnnotation("serve.emit"):
                    for (s, lane), r in lane_req.items():
                        P = len(r.prompt)
                        if t < P - 1:
                            cur[s, lane] = r.prompt[t + 1]
                        else:
                            tok = int(nxt[s, lane])
                            if not r.done:
                                r.tokens.append(tok)
                                generated += 1
                            cur[s, lane] = tok
                if all(r.done for r in lane_req.values()):
                    break
            if on_step is not None:
                on_step(steps)
            with TraceAnnotation("serve.dispatch",
                                 active_lanes=len(lane_req), lanes=cur.size):
                logits, greedy, cache = self._decode(self.params, pool.lora,
                                                     cache, jnp.asarray(cur),
                                                     pool.ranks)
            steps += 1
            t += 1
        jax.block_until_ready(logits)
        wall = time.perf_counter() - t0
        self.total_generated += generated
        self.total_decode_steps += steps
        self.total_wall_s += wall
        self.rounds += 1
        return RoundStats(requests=len(requests), generated=generated,
                          decode_steps=steps, wall_s=wall,
                          logits=logits_log)

    @property
    def aggregate_tok_s(self) -> float:
        return self.total_generated / max(self.total_wall_s, 1e-9)


def _request_spans(joiners) -> None:
    """One short ``serve.request`` event per joiner as its prefill is
    launched (block prefill) or its lane is reset to stream its prompt:
    whole milliseconds on the host clock from submit to lane
    (``queue_ms``) and from lane to this launch (``join_wait_ms``)."""
    launch = time.perf_counter()
    for r in joiners:
        with TraceAnnotation("serve.request",
                             queue_ms=round(1e3 * (r.join_t - r.submit_t)),
                             join_wait_ms=round(1e3 * (launch - r.join_t))):
            pass


def _fetch(greedy: jax.Array, logits: Optional[jax.Array] = None
           ) -> Tuple[np.ndarray, Optional[np.ndarray]]:
    """The step's argmax tokens (and its logits, where given) on the
    host."""
    with TraceAnnotation("serve.token_fetch"):
        return (np.asarray(greedy),
                None if logits is None else np.asarray(logits))
