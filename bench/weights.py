"""Seeded weights and token blocks, made by the benchmark on the device.

The layout (names, shapes, dtypes) is the program's parameter tree, read with
``jax.eval_shape`` and never its values.  Every leaf is drawn in one jitted
call from ``--seed``, in the dtype it is served in:

* a norm's weight ``w``: 1 + 0.1 N(0, 1), so that a norm that ignores its
  weight shows;
* a bias ``b``: 0.1 N(0, 1), so that a dropped bias shows;
* the embedding: 0.02 N(0, 1);
* every other matrix: N(0, 1 / fan_in).
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

from bench.traffic import MASK64


def key_for(seed: int, *stream: int) -> jax.Array:
    """A JAX key for any whole-number seed (more bits than 32) and a stream."""
    s = seed & MASK64
    key = jax.random.fold_in(jax.random.key(s & 0xFFFFFFFF), s >> 32)
    for x in stream:
        key = jax.random.fold_in(key, x)
    return key


def _draw(key, path, sd: jax.ShapeDtypeStruct):
    names = [str(getattr(p, "key", getattr(p, "idx", p))) for p in path]
    z = jax.random.normal(key, sd.shape, jnp.float32)
    if names[-1] == "w" and any("norm" in n for n in names):
        v = 1.0 + 0.1 * z
    elif names[-1] == "b":
        v = 0.1 * z
    elif names[0] == "embed":
        v = 0.02 * z
    else:
        v = z * (sd.shape[-2] ** -0.5)
    return v.astype(sd.dtype)


def make_params(shapes, seed: int, stream: int):
    """A tree like ``shapes`` (``jax.eval_shape`` of the program's init),
    filled from ``seed``; ``stream`` separates the modules of an app."""
    leaves, treedef = jax.tree_util.tree_flatten_with_path(shapes)

    @jax.jit
    def fill(key):
        return jax.tree_util.tree_unflatten(
            treedef,
            [_draw(jax.random.fold_in(key, i), path, sd) for i, (path, sd) in enumerate(leaves)],
        )

    return fill(key_for(seed, stream))


def make_tokens(seed: int, stream: int, batch: int, seq: int, vocab: int) -> jax.Array:
    """A seeded ``(batch, seq)`` block of token ids for one (module, batch)."""
    key = key_for(seed, 1_000_000 + stream, batch)
    return jax.random.randint(key, (batch, seq), 0, vocab, jnp.int32)
