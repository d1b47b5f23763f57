"""Grouped matmul of the experts' FFN: JAX's Pallas megablox ``gmm``.

``gmm(x, w, group_sizes)`` multiplies the rows of ``x`` (m, k),
sorted by expert, by their expert's ``w[e]`` (k, n): rows
``[sum(group_sizes[:e]), sum(group_sizes[:e+1]))`` go to expert ``e``.  It
is the kernel behind `ops.expert_gmm` on a TPU; ``jax.lax.ragged_dot``
computes the same product and is its oracle.

The Pallas call is made under this module's jitted ``expert_gmm``
(megablox's ``gmm`` is called unjitted), so the device trace names the
kernel ``expert_gmm``.  Tiling: an m tile of 256 or 512 rows against the whole
(k, n) of one expert where that fits the default scoped VMEM (16 MiB on a
v5e), so each m tile reads its rows once and consecutive tiles of one
expert reuse its weights without a new copy (on a v5e, 256 x 2048 x 1408
and 256 x 1408 x 2048 at Moonlight's widths, the fastest tilings measured;
1.3-1.4 ms a b32 call against 4.7 for ``ragged_dot``'s own TPU kernel, PERF.md).
The backward pass is ``ragged_dot``'s.
"""
from __future__ import annotations

import functools
import importlib

import jax
import jax.numpy as jnp

# megablox's ``gmm`` module (the package re-exports a differentiable wrapper
# of the same name over it)
_megablox = importlib.import_module("jax.experimental.pallas.ops.tpu.megablox.gmm")

VMEM_BYTES = 16 * 2**20  # default scoped VMEM limit of a v5e


def tiling(m: int, k: int, n: int, itemsize: int = 2) -> tuple[int, int, int]:
    """(tm, tk, tn) for an (m, k) x (k, n) group product; ``m`` divides by tm."""
    tm = next(t for t in (512, 256, 128, 64, 32, 16, 8) if m % t == 0)
    tk, tn = k, n
    while True:
        # double-buffered lhs and rhs tiles, the out tile and the f32
        # accumulator: on a v5e this separates the tilings its compiler
        # takes from those it refuses for lack of VMEM (tm <= 512)
        need = 2 * itemsize * (tm * tk + tk * tn) + itemsize * tm * tn + 4 * tm * tn
        if need < VMEM_BYTES:
            return tm, tk, tn
        if tm > 256 and m % (tm // 2) == 0:
            tm //= 2
        elif tn % 256 == 0:  # a narrower n tile re-reads rows, not weights
            tn //= 2
        elif tk % 256 == 0:
            tk //= 2
        else:
            return tm, tk, tn


@functools.partial(jax.jit, static_argnames=("interpret",))
def expert_gmm(x, w, group_sizes, interpret=False):
    m, k = x.shape
    n = w.shape[-1]
    pad = -m % 8
    if pad:  # rows of zeros, counted in the last group and sliced away
        x = jnp.concatenate([x, jnp.zeros((pad, k), x.dtype)])
        group_sizes = group_sizes.at[-1].add(pad)
    out = _megablox.gmm.__wrapped__(
        x, w, group_sizes.astype(jnp.int32),
        preferred_element_type=x.dtype,
        tiling=tiling(m + pad, k, n, x.dtype.itemsize),
        interpret=interpret,
    )
    return out[:m]


@functools.partial(jax.custom_vjp, nondiff_argnums=(3,))
def gmm(x, w, group_sizes, interpret=False):
    return expert_gmm(x, w, group_sizes, interpret=interpret)


def _fwd(x, w, group_sizes, interpret):
    return expert_gmm(x, w, group_sizes, interpret=interpret), (x, w, group_sizes)


def _bwd(interpret, res, g):
    x, w, group_sizes = res
    _, vjp = jax.vjp(lambda x, w: jax.lax.ragged_dot(x, w, group_sizes), x, w)
    return (*vjp(g), None)


gmm.defvjp(_fwd, _bwd)
