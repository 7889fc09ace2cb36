"""Faults planted under the timed path, to show that the correctness
comparison catches them: on the chip at a cell's own size
(``bench/calibrate.py --fault``) and on the CPU at the rehearsal's size
(``bench/tests/test_faults.py``). Each ``plant`` patches the program in
this process and returns a function that takes the patch out again."""
from __future__ import annotations

from typing import Callable


def _patch(obj, name: str, value) -> Callable[[], None]:
    old = getattr(obj, name)
    setattr(obj, name, value)
    return lambda: setattr(obj, name, old)


def unchanged_state() -> Callable[[], None]:
    """The train step returns the adapters and optimizer state it was
    given (its losses are still computed)."""
    import jax
    import jax.numpy as jnp
    from repro.core import steps as STEPS
    real = STEPS.jit_train_step

    def jit_train_step(cfg, **kw):
        step = real(cfg, **kw)

        def broken(params, lora, opt, hp, active, ranks, batch):
            copy = lambda t: jax.tree_util.tree_map(jnp.copy, t)  # noqa: E731
            _, _, metrics = step(params, copy(lora), copy(opt), hp, active,
                                 ranks, batch)
            return lora, opt, metrics
        return broken

    return _patch(STEPS, "jit_train_step", jit_train_step)


def half_batch() -> Callable[[], None]:
    """Half of each slot's rows are left out of the step: the labels of
    every other row are masked, so the loss is the mean over the rest
    (every other row, so a slot packed narrower than the batch loses half
    of its real rows too)."""
    from repro.core import steps as STEPS
    real = STEPS.jit_train_step

    def jit_train_step(cfg, **kw):
        step = real(cfg, **kw)

        def broken(params, lora, opt, hp, active, ranks, batch):
            batch = dict(batch)
            lab = batch["labels"]
            batch["labels"] = lab.at[:, 1::2].set(-1)
            return step(params, lora, opt, hp, active, ranks, batch)
        return broken

    return _patch(STEPS, "jit_train_step", jit_train_step)


def stale_moments() -> Callable[[], None]:
    """A slot's optimizer state is not reset when its job leaves or a new
    one comes in: an admitted job inherits the former occupant's Adam
    moments and step count."""
    from repro.optim import adamw
    return _patch(adamw, "reset_slot", lambda state, slot: state)


def _keep_on_refill(attr: str) -> Callable[[], None]:
    """Eviction, and admission into a slot that a job held before, leave
    ``SlotManager.<attr>`` as it was."""
    from repro.core.adapter_state import SlotManager
    admit, evict = SlotManager.admit, SlotManager.evict
    held = set()

    def admit_(self, slot, *a, **kw):
        keep = getattr(self, attr)
        admit(self, slot, *a, **kw)
        if (id(self), slot) in held:
            setattr(self, attr, keep)
        held.add((id(self), slot))

    def evict_(self, slot):
        keep = getattr(self, attr)
        evict(self, slot)
        setattr(self, attr, keep)

    undo = [_patch(SlotManager, "admit", admit_),
            _patch(SlotManager, "evict", evict_)]
    return lambda: [u() for u in undo]


def stale_adapter() -> Callable[[], None]:
    """A refilled slot keeps the adapter of the job that held it before,
    in place of a fresh one (B = 0)."""
    return _keep_on_refill("lora")


def stale_rank() -> Callable[[], None]:
    """A refilled slot keeps the rank mask of the job that held it
    before."""
    return _keep_on_refill("ranks")


def altered_token() -> Callable[[], None]:
    """One served token of every request is replaced by the next token id
    where the replica hands the request back."""
    from repro.serve.replica import ServingReplica
    real = ServingReplica._complete

    def _complete(self, coord, r):
        i = len(r.tokens) // 2
        r.tokens[i] = (r.tokens[i] + 1) % self.cfg.vocab_size
        return real(self, coord, r)

    return _patch(ServingReplica, "_complete", _complete)


FAULTS = {"unchanged_state": unchanged_state, "half_batch": half_batch,
          "stale_moments": stale_moments, "stale_adapter": stale_adapter,
          "stale_rank": stale_rank, "altered_token": altered_token}
