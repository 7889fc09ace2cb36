"""Production training launcher: pjit multi-LoRA training on a real mesh.

    PYTHONPATH=src python -m repro.launch.train --arch stablelm-3b \
        --shape train_4k --steps 10 [--reduced] [--mesh dxm]

On TPU hardware this builds the (data, model) mesh over the real devices
and runs the Adapter-Parallel train step with the production sharding
rules; on this CPU container use ``--reduced`` (tiny variant of the same
architecture, 1x1 mesh) for a functional end-to-end pass. The step function,
sharding rules, and data layout are identical in both modes — only the mesh
and the config dims change.
"""
from __future__ import annotations

import argparse
import dataclasses
import time

import jax
import jax.numpy as jnp
import numpy as np

from repro.configs.registry import ASSIGNED, get_arch
from repro.configs.shapes import get_shape
from repro.core import lora as LORA
from repro.data.synthetic import SlotBatcher, make_task_dataset
from repro.launch import partitioning as PT
from repro.launch import steps_dist
from repro.launch.mesh import make_mesh
from repro.models import model as M
from repro.optim import adamw


def build_mesh(spec: str) -> jax.sharding.Mesh:
    d, m = (int(x) for x in spec.split("x"))
    return make_mesh((d, m), ("data", "model"), jax.devices()[:d * m])


class AdapterParallelRun:
    """One adapter-parallel training run on ``mesh``.

    The frozen backbone, the Z slot adapters (one per entry of ``ranks``),
    their optimizer state and per-slot hyperparameters are created in
    their production shardings — each device computes only its own share
    — and ``step`` runs the jitted train step with the adapter state
    donated. ``lora`` (a [L, Z, ...] slot tree) replaces the seeded
    adapter init, so runs on different meshes can start from the same
    adapters."""

    def __init__(self, cfg, mesh: jax.sharding.Mesh, ranks, *,
                 lr: float = 1e-3, seed: int = 0, lora=None):
        Z = len(ranks)
        self.mesh = mesh
        self.ranks = jnp.asarray([min(r, cfg.lora.r_max) for r in ranks],
                                 jnp.int32)
        key = jax.random.PRNGKey(seed)

        def make(key, lora):
            params = M.init_params(key, cfg)
            if lora is None:
                lora = LORA.init_lora_tree(key, cfg, Z, self.ranks,
                                           M.target_shapes(cfg))
            return params, lora, adamw.init_state(lora, Z)

        p, l, o = jax.eval_shape(make, key, lora)
        ns = lambda t: PT.to_named(mesh, t)
        p_sh = ns(PT.base_param_specs(mesh, p))
        l_sh = ns(PT.lora_param_specs(mesh, l))
        o_sh = ns(PT.opt_state_specs(mesh, o))
        self.params, self.lora, self.opt = jax.jit(
            make, out_shardings=(p_sh, l_sh, o_sh))(key, lora)
        hp = adamw.SlotHParams.broadcast(Z, lr=lr)
        self.hp = jax.device_put(hp, ns(PT.hp_specs(mesh, hp)))
        v_sh = PT.to_named(mesh, PT.pick_spec(mesh, (Z,), [{0: "data"}, {}]))
        self.active = jax.device_put(jnp.ones((Z,), jnp.int32), v_sh)
        self.ranks = jax.device_put(self.ranks, v_sh)
        self._step = jax.jit(steps_dist.make_train_step(cfg, mesh),
                             out_shardings=(l_sh, o_sh, None),
                             donate_argnums=(1, 2))

    def step(self, tokens, labels) -> dict:
        """One fused step on a [Z, b, S] batch; returns its metrics."""
        batch = {"tokens": jnp.asarray(tokens), "labels": jnp.asarray(labels)}
        batch = jax.device_put(
            batch, PT.to_named(self.mesh, PT.batch_specs(self.mesh, batch)))
        with self.mesh:
            self.lora, self.opt, metrics = self._step(
                self.params, self.lora, self.opt, self.hp, self.active,
                self.ranks, batch)
        return metrics


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default="stablelm-3b",
                    choices=ASSIGNED + ["paper-llama-tiny"])
    ap.add_argument("--shape", default="train_4k")
    ap.add_argument("--steps", type=int, default=10)
    ap.add_argument("--reduced", action="store_true",
                    help="tiny variant of the arch (CPU-runnable)")
    ap.add_argument("--mesh", default="1x1")
    ap.add_argument("--lr", type=float, default=1e-3)
    ap.add_argument("--rank", type=int, default=8)
    args = ap.parse_args()

    cfg = get_arch(args.arch)
    shape = get_shape(args.shape)
    if args.reduced:
        cfg = dataclasses.replace(cfg.reduced(), dtype="float32")
        Z, b, S = 4, 2, 64
    else:
        Z, b = shape.decompose()
        S = shape.seq_len
    mesh = build_mesh(args.mesh)
    print(f"arch={cfg.name} Z={Z} b={b} S={S} "
          f"mesh={dict(mesh.shape)} devices={len(jax.devices())}")

    run = AdapterParallelRun(cfg, mesh, [args.rank] * Z, lr=args.lr)
    ds = make_task_dataset("launch", cfg.vocab_size, seq_len=S,
                           num_train=max(4 * Z * b, 64), difficulty=0.3)
    batcher = SlotBatcher(ds, Z, b)
    for t in range(args.steps):
        tokens, labels = batcher.next_batch()
        t0 = time.time()
        metrics = run.step(tokens, labels)
        loss = np.asarray(metrics["per_slot_loss"])
        print(f"step {t:4d}  {time.time() - t0:6.2f}s  "
              f"loss/slot: {np.array2string(loss, precision=3)}")
    print("done")


if __name__ == "__main__":
    main()
