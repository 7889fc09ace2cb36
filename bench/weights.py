"""Weights and adapters made from the seed, by the benchmark itself.

Both the program and the reference read these: the program never makes a
weight the reference relies on. The backbone is made on the device in one
jitted call, in the type it is served in, in the program's layout (which
``check_layout`` compares with the program's own ``init_params`` shapes).
"""
from __future__ import annotations

import functools
from typing import Dict, List, Tuple

# the adapters' B starts random (std B_SCALE / sqrt(d_model)), so their
# output counts from the first step; A as the trainer draws it
B_SCALE = 0.5
NORM_JITTER = 0.1       # norm weights 1 + N(0, 0.1): the norms' scales count


def key(seed: int):
    """A PRNG key from any whole seed (``PRNGKey`` keeps 32 bits)."""
    import jax
    k = jax.random.PRNGKey(seed & 0xFFFFFFFF)
    return jax.random.fold_in(k, (seed >> 32) & 0x7FFFFFFF)


def dims(spec: Dict):
    d = spec["hidden_size"]
    H, KV = spec["num_attention_heads"], spec["num_key_value_heads"]
    hd = spec.get("head_dim") or d // H
    return (spec["num_hidden_layers"], d, H, KV, hd,
            spec["intermediate_size"], spec["vocab_size"])


def target_shapes(spec: Dict) -> Dict[str, Tuple[int, int]]:
    return {t: s for t, s in projections(spec).items()
            if t in spec["lora"]["targets"]}


def projections(spec: Dict) -> Dict[str, Tuple[int, int]]:
    """(d_in, d_out) of every projection of a layer."""
    L, d, H, KV, hd, ff, V = dims(spec)
    return {"q_proj": (d, H * hd), "k_proj": (d, KV * hd),
            "v_proj": (d, KV * hd), "o_proj": (H * hd, d),
            "gate_proj": (d, ff), "up_proj": (d, ff), "down_proj": (ff, d)}


def make_params(spec: Dict, seed: int) -> Dict:
    """The frozen backbone: projections N(0, 1/fan_in), embedding
    N(0, 0.02^2), norm weights 1 + N(0, 0.1^2) in float32."""
    import jax
    import jax.numpy as jnp
    L, d, H, KV, hd, ff, V = dims(spec)
    dt = jnp.dtype(spec["torch_dtype"])
    shapes = projections(spec)

    def build(k):
        ks = iter(jax.random.split(k, 16))

        def proj(shape, fan_in):
            return (jax.random.normal(next(ks), shape, dt)
                    * jnp.asarray(fan_in ** -0.5, dt))

        def norm(shape):
            return 1.0 + NORM_JITTER * jax.random.normal(next(ks), shape,
                                                         jnp.float32)

        layers = {"attn_norm": norm((L, d)), "mlp_norm": norm((L, d))}
        for t, (din, dout) in shapes.items():
            layers[t] = proj((L, din, dout), din)
        params = {"embed": jax.random.normal(next(ks), (V, d), dt)
                  * jnp.asarray(0.02, dt),
                  "layers": layers, "final_norm": norm((d,))}
        if not spec["tie_word_embeddings"]:
            params["lm_head"] = proj((d, V), d)
        return params

    return jax.jit(build)(key(seed))


def check_layout(params: Dict, program_shapes: Dict) -> None:
    """The benchmark's weights must have the program's layout."""
    import jax
    mine = jax.tree_util.tree_map(lambda x: (x.shape, str(x.dtype)), params)
    theirs = jax.tree_util.tree_map(lambda x: (tuple(x.shape), str(x.dtype)),
                                    program_shapes)
    if mine != theirs:
        raise ValueError(f"weight layout differs from the program's: "
                         f"{mine} vs {theirs}")


def adapters(spec: Dict, ranks: List[int], seed: int, stream: int = 1) -> Dict:
    """Adapters on a slot axis, {target: {"A": [L, Z, d_in, r_max],
    "B": [L, Z, r_max, d_out]}} in float32, made on the device in one
    jitted call: A ~ N(0, 1/r_max) as the trainer draws it, B ~
    N(0, B_SCALE^2 / d_model), both zero beyond each slot's rank.
    ``stream`` keeps sets of adapters apart (the first wave's, an admitted
    job's); the builder is compiled once per shape, so a call with other
    ranks compiles nothing."""
    import jax
    import jax.numpy as jnp
    shapes = tuple(target_shapes(spec).items())
    build = _adapter_builder(spec["num_hidden_layers"], spec["hidden_size"],
                             spec["lora"]["r_max"], shapes)
    k = jax.random.fold_in(key(seed), stream)   # apart from the backbone's
    return build(k, jnp.asarray(ranks, jnp.int32))


@functools.lru_cache(maxsize=None)
def _adapter_builder(L: int, d: int, r_max: int, shapes: Tuple):
    import jax
    import jax.numpy as jnp

    def build(k, ranks):
        Z = ranks.shape[0]
        keep = (jnp.arange(r_max)[None, :] < ranks[:, None]).astype(
            jnp.float32)                                     # [Z, r]
        ks = iter(jax.random.split(k, 2 * len(shapes)))
        out = {}
        for t, (din, dout) in shapes:
            A = jax.random.normal(next(ks), (L, Z, din, r_max)) * r_max ** -0.5
            B = jax.random.normal(next(ks), (L, Z, r_max, dout)) * (
                B_SCALE / d ** 0.5)
            out[t] = {"A": A * keep[None, :, None, :],
                      "B": B * keep[None, :, :, None]}
        return out

    return jax.jit(build)


def slot(tree: Dict, z: int) -> Dict:
    """One adapter ([L, ...] leaves) of a slot-axis tree."""
    return {t: {m: ab[m][:, z] for m in ab} for t, ab in tree.items()}
