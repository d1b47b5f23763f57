"""Pallas TPU fused selective-scan (Mamba core).

TPU adaptation (vs. the CUDA selective-scan): the recurrence is kept
sequential in time but fully vectorized over the channel dimension — each
grid step owns a (CL, D) chunk of the sequence, carries the (N, D) state in
VMEM scratch (D on the 128-wide lane axis), and fuses the discretization
``a = exp(dt * A)``, the recurrence and the output contraction
``y = C . h (+ D x)`` so only x/dt/B/C stream from HBM and only y streams
back — the kernel is HBM-bandwidth-bound exactly like the original.

Oracle: `repro.kernels.ref.selective_scan`.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu


def _kernel(
    x_ref,  # (1, CL, D)
    dt_ref,  # (1, CL, D)
    at_ref,  # (N, D)  = A transposed
    b_ref,  # (1, CL, N)
    c_ref,  # (1, CL, N)
    h0_ref,  # (1, N, D)
    y_ref,  # (1, CL, D)
    hl_ref,  # (1, N, D)
    h_ref,  # VMEM scratch (N, D) f32
    *,
    cl: int,
    nc: int,
):
    ci = pl.program_id(1)

    @pl.when(ci == 0)
    def _init():
        h_ref[:] = h0_ref[0].astype(jnp.float32)

    at = at_ref[:].astype(jnp.float32)  # (N, D)

    def step(t, h):
        dt = dt_ref[0, t].astype(jnp.float32)  # (D,)
        x = x_ref[0, t].astype(jnp.float32)  # (D,)
        bt = b_ref[0, t].astype(jnp.float32)  # (N,)
        ct = c_ref[0, t].astype(jnp.float32)  # (N,)
        a = jnp.exp(dt[None, :] * at)  # (N, D)
        h = a * h + (dt * x)[None, :] * bt[:, None]
        y = jnp.sum(h * ct[:, None], axis=0)  # (D,)
        y_ref[0, pl.ds(t, 1), :] = y[None].astype(y_ref.dtype)
        return h

    h = jax.lax.fori_loop(0, cl, step, h_ref[:])
    h_ref[:] = h

    @pl.when(ci == nc - 1)
    def _fin():
        hl_ref[0] = h.astype(hl_ref.dtype)


@functools.partial(jax.jit, static_argnames=("chunk", "interpret"))
def chunked_selective_scan(
    x: jax.Array,  # (B, L, D) post-conv activations
    dt: jax.Array,  # (B, L, D) softplus'd step sizes
    A: jax.Array,  # (D, N) negative decay rates
    Bm: jax.Array,  # (B, L, N)
    Cm: jax.Array,  # (B, L, N)
    h0: jax.Array | None = None,  # (B, N, D) NOTE: transposed state layout
    *,
    chunk: int = 128,
    interpret: bool = False,
) -> tuple[jax.Array, jax.Array]:
    """Returns (y (B, L, D), h_last (B, N, D))."""
    B, L, D = x.shape
    N = A.shape[1]
    cl = min(chunk, L)
    assert L % cl == 0, (L, cl)
    nc = L // cl
    if h0 is None:
        h0 = jnp.zeros((B, N, D), jnp.float32)
    at = A.T.astype(jnp.float32)  # (N, D): D on lanes

    y, h_last = pl.pallas_call(
        functools.partial(_kernel, cl=cl, nc=nc),
        grid=(B, nc),
        in_specs=[
            pl.BlockSpec((1, cl, D), lambda b, ci: (b, ci, 0)),
            pl.BlockSpec((1, cl, D), lambda b, ci: (b, ci, 0)),
            pl.BlockSpec((N, D), lambda b, ci: (0, 0)),
            pl.BlockSpec((1, cl, N), lambda b, ci: (b, ci, 0)),
            pl.BlockSpec((1, cl, N), lambda b, ci: (b, ci, 0)),
            pl.BlockSpec((1, N, D), lambda b, ci: (b, 0, 0)),
        ],
        out_specs=[
            pl.BlockSpec((1, cl, D), lambda b, ci: (b, ci, 0)),
            pl.BlockSpec((1, N, D), lambda b, ci: (b, 0, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((B, L, D), x.dtype),
            jax.ShapeDtypeStruct((B, N, D), jnp.float32),
        ],
        scratch_shapes=[pltpu.VMEM((N, D), jnp.float32)],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "arbitrary")
        ),
        interpret=interpret,
    )(x, dt, at, Bm, Cm, h0)
    return y, h_last
