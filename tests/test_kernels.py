"""Pallas kernels vs pure-jnp oracles: shape/dtype sweeps in interpret mode."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

# JAX-compile-heavy (jits real kernels/models); deselect with -m "not slow"
pytestmark = pytest.mark.slow

from repro.kernels import ops, ref
from repro.kernels.decode_attention import flash_decode
from repro.kernels.flash_attention import fit_block, flash_attention
from repro.kernels.rmsnorm import fused_rmsnorm
from repro.kernels.ssm_scan import chunked_selective_scan


def rel_err(a, b):
    a = np.asarray(a, np.float32)
    b = np.asarray(b, np.float32)
    return np.max(np.abs(a - b)) / (np.max(np.abs(b)) + 1e-9)


TOL = {jnp.float32: 2e-5, jnp.bfloat16: 2e-2}


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
@pytest.mark.parametrize(
    "B,S,Hq,Hkv,D,window",
    [
        (1, 128, 1, 1, 64, None),
        (2, 256, 4, 2, 64, None),
        (2, 256, 4, 1, 128, None),  # MQA
        (1, 384, 6, 2, 128, 128),  # sliding window
        (2, 128, 8, 8, 256, None),  # MHA, gemma head_dim
    ],
)
def test_flash_attention_sweep(B, S, Hq, Hkv, D, window, dtype):
    ks = jax.random.split(jax.random.key(0), 3)
    q = jax.random.normal(ks[0], (B, S, Hq, D), dtype)
    k = jax.random.normal(ks[1], (B, S, Hkv, D), dtype)
    v = jax.random.normal(ks[2], (B, S, Hkv, D), dtype)
    out = flash_attention(q, k, v, causal=True, window=window, interpret=True)
    exp = ref.attention(q, k, v, causal=True, window=window)
    assert rel_err(out, exp) < TOL[dtype]


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
@pytest.mark.parametrize(
    "B,S,Hq,Hkv,Dk,Dv,window",
    [
        (2, 256, 4, 2, 64, 64, None),
        (3, 512, 4, 1, 128, 128, None),  # MQA
        (2, 256, 8, 8, 64, 64, 100),  # window
        (1, 256, 4, 1, 192, 128, None),  # MLA-absorbed: Dk != Dv
    ],
)
def test_flash_decode_sweep(B, S, Hq, Hkv, Dk, Dv, window, dtype):
    ks = jax.random.split(jax.random.key(1), 3)
    q = jax.random.normal(ks[0], (B, Hq, Dk), dtype)
    kc = jax.random.normal(ks[1], (B, S, Hkv, Dk), dtype)
    vc = jax.random.normal(ks[2], (B, S, Hkv, Dv), dtype)
    lengths = jnp.asarray([(S * (i + 1)) // (B + 1) + 1 for i in range(B)], jnp.int32)
    out = flash_decode(q, kc, vc, lengths, window=window, block_k=128, interpret=True)
    exp = ref.decode_attention(q, kc, vc, lengths, window=window)
    assert rel_err(out, exp) < TOL[dtype]


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
@pytest.mark.parametrize("B,L,D,N,chunk", [(2, 256, 32, 8, 64), (1, 128, 64, 16, 128)])
def test_selective_scan_sweep(B, L, D, N, chunk, dtype):
    ks = jax.random.split(jax.random.key(2), 5)
    x = (jax.random.normal(ks[0], (B, L, D)) * 0.5).astype(dtype)
    dt = jax.nn.softplus(jax.random.normal(ks[1], (B, L, D))).astype(dtype)
    A = -jnp.exp(jax.random.normal(ks[2], (D, N)) * 0.3)
    Bm = jax.random.normal(ks[3], (B, L, N)).astype(dtype)
    Cm = jax.random.normal(ks[4], (B, L, N)).astype(dtype)
    h0 = jnp.zeros((B, N, D), jnp.float32)
    y, h = chunked_selective_scan(x, dt, A, Bm, Cm, h0, chunk=chunk, interpret=True)
    y2, h2 = ref.selective_scan(x, dt, A, Bm, Cm, h0)
    assert rel_err(y, y2) < TOL[dtype]
    assert rel_err(h, h2) < TOL[dtype]


def test_selective_scan_carries_state():
    """Scanning two halves with carried state == scanning the whole sequence."""
    B, L, D, N = 1, 128, 16, 8
    ks = jax.random.split(jax.random.key(3), 5)
    x = jax.random.normal(ks[0], (B, L, D)) * 0.5
    dt = jax.nn.softplus(jax.random.normal(ks[1], (B, L, D)))
    A = -jnp.exp(jax.random.normal(ks[2], (D, N)) * 0.3)
    Bm = jax.random.normal(ks[3], (B, L, N))
    Cm = jax.random.normal(ks[4], (B, L, N))
    y_full, h_full = ref.selective_scan(x, dt, A, Bm, Cm)
    h = None
    ys = []
    for sl in (slice(0, 64), slice(64, 128)):
        y, h = ref.selective_scan(x[:, sl], dt[:, sl], A, Bm[:, sl], Cm[:, sl], h)
        ys.append(y)
    np.testing.assert_allclose(
        np.asarray(jnp.concatenate(ys, 1)), np.asarray(y_full), rtol=1e-5, atol=1e-5
    )


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
@pytest.mark.parametrize("shape,gemma", [((4, 7, 128), False), ((2, 256), True), ((3, 3, 3, 256), False)])
def test_rmsnorm_sweep(shape, gemma, dtype):
    x = jax.random.normal(jax.random.key(4), shape, dtype)
    w = jax.random.normal(jax.random.key(5), (shape[-1],), dtype)
    out = fused_rmsnorm(x, w, gemma=gemma, interpret=True, block_rows=8)
    exp = ref.rmsnorm(x, w, gemma=gemma)
    assert rel_err(out, exp) < TOL[dtype]


def test_mlstm_parallel_equals_recurrent():
    """ref.mlstm_chunked vs a step-by-step recurrence."""
    B, L, H, D = 1, 16, 2, 8
    ks = jax.random.split(jax.random.key(6), 5)
    q = jax.random.normal(ks[0], (B, L, H, D))
    k = jax.random.normal(ks[1], (B, L, H, D))
    v = jax.random.normal(ks[2], (B, L, H, D))
    li = jax.random.normal(ks[3], (B, L, H))
    lf = jax.nn.log_sigmoid(jax.random.normal(ks[4], (B, L, H)) + 1.0)
    out = ref.mlstm_chunked(q, k, v, li, lf)

    # sequential reference
    C = jnp.zeros((B, H, D, D))
    n = jnp.zeros((B, H, D))
    m = jnp.full((B, H), -1e30)
    outs = []
    for t in range(L):
        m_new = jnp.maximum(lf[:, t] + m, li[:, t])
        i_s = jnp.exp(li[:, t] - m_new)
        f_s = jnp.exp(lf[:, t] + m - m_new)
        kf = k[:, t] * (D ** -0.5)
        C = f_s[..., None, None] * C + i_s[..., None, None] * kf[..., :, None] * v[:, t][..., None, :]
        n = f_s[..., None] * n + i_s[..., None] * kf
        num = jnp.einsum("bhd,bhdv->bhv", q[:, t], C)
        den = jnp.maximum(jnp.abs(jnp.einsum("bhd,bhd->bh", q[:, t], n)), jnp.exp(-m_new))
        outs.append(num / den[..., None])
        m = m_new
    exp = jnp.stack(outs, 1)
    assert rel_err(out, exp) < 1e-4


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
@pytest.mark.parametrize("B,L,H,D,chunk", [(2, 128, 2, 32, 32), (1, 256, 4, 64, 128)])
def test_chunked_mlstm_sweep(B, L, H, D, chunk, dtype):
    from repro.kernels.mlstm_chunk import chunked_mlstm

    ks = jax.random.split(jax.random.key(7), 5)
    q = jax.random.normal(ks[0], (B, L, H, D), dtype)
    k = jax.random.normal(ks[1], (B, L, H, D), dtype)
    v = jax.random.normal(ks[2], (B, L, H, D), dtype)
    li = (jax.random.normal(ks[3], (B, L, H)) * 0.5).astype(dtype)
    lf = jax.nn.log_sigmoid(jax.random.normal(ks[4], (B, L, H)) + 1.0).astype(dtype)
    out = chunked_mlstm(q, k, v, li, lf, chunk=chunk, interpret=True)
    exp = ref.mlstm_chunked(q, k, v, li, lf)
    assert rel_err(out, exp) < (3e-2 if dtype == jnp.bfloat16 else 2e-4)


@pytest.mark.parametrize(
    "n,block,expect",
    [(128, 128, 128), (64, 128, 64), (7, 128, 7), (1024, 512, 512),
     (640, 512, 128), (200, 128, 8)],
)
def test_fit_block(n, block, expect):
    """The block tiles the axis and is the whole axis or a multiple of 8."""
    b = fit_block(n, block)
    assert b == expect and n % b == 0


def test_rmsnorm_takes_kernel_at_unaligned_width(monkeypatch):
    """Width 960 (smollm) runs the fused kernel, not the `ref` fallback."""
    monkeypatch.setattr(ops, "_mode", lambda: "interpret")
    monkeypatch.setattr(ops, "TAKEN", type(ops.TAKEN)())
    x = jax.random.normal(jax.random.key(8), (2, 8, 960), jnp.bfloat16)
    w = jax.random.normal(jax.random.key(9), (960,), jnp.bfloat16)
    out = ops.rmsnorm(x, w)
    assert dict(ops.TAKEN) == {("rmsnorm", "interpret"): 1}
    assert rel_err(out, ref.rmsnorm(x, w)) < TOL[jnp.bfloat16]


def test_flash_decode_multi_kv_heads_untiled_length():
    """Hkv > 1 over a cache whose length is not a multiple of block_k."""
    B, S, Hq, Hkv, D = 2, 640, 6, 3, 64
    ks = jax.random.split(jax.random.key(10), 3)
    q = jax.random.normal(ks[0], (B, Hq, D))
    kc = jax.random.normal(ks[1], (B, S, Hkv, D))
    vc = jax.random.normal(ks[2], (B, S, Hkv, D))
    lengths = jnp.asarray([300, 640], jnp.int32)
    out = flash_decode(q, kc, vc, lengths, interpret=True)
    exp = ref.decode_attention(q, kc, vc, lengths)
    assert rel_err(out, exp) < TOL[jnp.float32]
