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
        # the served widths, and whole-axis blocks:
        (3, 128, 15, 5, 64, None),  # smollm-360m GQA 15/5
        (2, 128, 20, 20, 128, None),  # qwen1.5-4b MHA 20/20
        (2, 64, 4, 2, 64, None),  # a whole-axis block under 128
        (2, 120, 6, 2, 64, None),  # ... not a multiple of 8 either
        (2, 128, 4, 2, 64, 32),  # window shorter than the sequence
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


def test_attention_sends_one_block_sequences_to_xla(monkeypatch):
    """Where the kernels run, a sequence that one 128 block holds takes the
    XLA attention and a longer one the kernel, each counted in TAKEN."""
    monkeypatch.setattr(ops, "_mode", lambda: "interpret")
    monkeypatch.setattr(ops, "TAKEN", type(ops.TAKEN)())
    for S in (128, 256):
        ks = jax.random.split(jax.random.key(S), 3)
        q = jax.random.normal(ks[0], (1, S, 2, 64))
        k, v = (jax.random.normal(kk, (1, S, 1, 64)) for kk in ks[1:])
        assert rel_err(ops.attention(q, k, v), ref.attention(q, k, v)) < TOL[jnp.float32]
    assert dict(ops.TAKEN) == {("attention", "xla"): 1, ("attention", "interpret"): 1}


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
@pytest.mark.parametrize(
    "B,S,Hq,Hkv,D,window",
    [
        (3, 128, 15, 5, 64, None),  # smollm-360m GQA 15/5
        (2, 128, 20, 20, 128, None),  # qwen1.5-4b MHA 20/20
        (2, 120, 6, 2, 64, None),  # a sequence under one block
        (2, 128, 4, 2, 64, 32),  # window shorter than the sequence
    ],
)
def test_xla_attention_sweep(B, S, Hq, Hkv, D, window, dtype):
    ks = jax.random.split(jax.random.key(1), 3)
    q = jax.random.normal(ks[0], (B, S, Hq, D), dtype)
    k = jax.random.normal(ks[1], (B, S, Hkv, D), dtype)
    v = jax.random.normal(ks[2], (B, S, Hkv, D), dtype)
    out = ops._xla_attention(q, k, v, causal=True, window=window, scale=None)
    assert out.dtype == dtype
    assert rel_err(out, ref.attention(q, k, v, causal=True, window=window)) < TOL[dtype]


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


def _attention_f64(q, k, v, scale):
    """Causal softmax attention in float64, one head per query head."""
    q, k, v = (np.asarray(t, np.float64) for t in (q, k, v))
    s = np.einsum("bqhd,bkhd->bhqk", q, k) * scale
    S = q.shape[1]
    s = np.where(np.tril(np.ones((S, S), bool)), s, -np.inf)
    p = np.exp(s - s.max(-1, keepdims=True))
    p /= p.sum(-1, keepdims=True)
    return np.einsum("bhqk,bkhd->bqhd", p, v)


@pytest.mark.parametrize("S", [128, 64])
def test_mla_prefill_attention_takes_xla_at_one_block(monkeypatch, S):
    """MLA's prefill attention (q/k heads 128 + 64 = 192 wide, v heads 128)
    goes through `attention`'s dispatch: XLA ops at stated precision where one
    block holds the sequence, against a float64 attention."""
    monkeypatch.setattr(ops, "_mode", lambda: "interpret")
    monkeypatch.setattr(ops, "TAKEN", type(ops.TAKEN)())
    B, H, dn, dr, dv = 2, 4, 128, 64, 128
    ks = jax.random.split(jax.random.key(S), 5)
    # bf16-exact float32 operands: what remains is the attention's own error
    mk = lambda k, shape: jax.random.normal(k, shape).astype(jnp.bfloat16).astype(jnp.float32)
    qn, qr = mk(ks[0], (B, S, H, dn)), mk(ks[1], (B, S, H, dr))
    kn, kr, v = mk(ks[2], (B, S, H, dn)), mk(ks[3], (B, S, dr)), mk(ks[4], (B, S, H, dv))
    scale = (dn + dr) ** -0.5
    out = ops.mla_prefill_attention(qn, qr, kn, kr, v, scale=scale)
    q = jnp.concatenate([qn, qr], -1)
    k = jnp.concatenate([kn, jnp.broadcast_to(kr[:, :, None], (B, S, H, dr))], -1)
    assert out.shape == (B, S, H, dv)
    assert rel_err(out, _attention_f64(q, k, v, scale)) < TOL[jnp.float32]
    assert dict(ops.TAKEN) == {("attention", "xla"): 1}


def test_mla_prefill_attention_on_the_cpu_takes_ref(monkeypatch):
    monkeypatch.setattr(ops, "TAKEN", type(ops.TAKEN)())
    B, S, H = 1, 16, 2
    ks = jax.random.split(jax.random.key(3), 5)
    qn, kn, v = (jax.random.normal(kk, (B, S, H, 32)) for kk in ks[:3])
    qr, kr = jax.random.normal(ks[3], (B, S, H, 16)), jax.random.normal(ks[4], (B, S, 16))
    ops.mla_prefill_attention(qn, qr, kn, kr, v, scale=48 ** -0.5)
    assert dict(ops.TAKEN) == {("attention", "ref"): 1}


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
@pytest.mark.parametrize("m,k,n,E", [(96, 64, 48, 8), (256, 128, 128, 16), (60, 32, 16, 4)])
def test_expert_gmm_matches_ragged_dot(m, k, n, E, dtype):
    """The megablox grouped matmul (interpret mode) against ragged_dot, with
    empty groups and a row count that is not a multiple of 8."""
    from repro.kernels.expert_gmm import gmm

    ks = jax.random.split(jax.random.key(m), 3)
    x = jax.random.normal(ks[0], (m, k), dtype)
    w = (jax.random.normal(ks[1], (E, k, n)) * k ** -0.5).astype(dtype)
    sizes = jnp.bincount(jax.random.randint(ks[2], (m,), 0, E // 2) * 2, length=E)  # odd experts empty
    out = gmm(x, w, sizes.astype(jnp.int32), interpret=True)
    assert out.shape == (m, n) and out.dtype == dtype
    assert rel_err(out, jax.lax.ragged_dot(x, w, sizes)) < TOL[dtype]


def test_expert_gmm_tiling_fits_scoped_vmem():
    from repro.kernels.expert_gmm import tiling

    # Moonlight's widths: one m tile of 256 rows against an expert's whole
    # (k, n), the fastest tiling measured on a v5e (PERF.md)
    for m in (768, 6144, 24576):
        assert tiling(m, 2048, 1408) == (256, 2048, 1408)
        assert tiling(m, 1408, 2048) == (256, 1408, 2048)
    # narrower widths keep 512-row tiles; a width too wide for one tile halves n
    assert tiling(1024, 512, 512) == (512, 512, 512)
    tm, tk, tn = tiling(4096, 4096, 4096)
    assert 4096 % tm == 0 and tk == 4096 and 4096 % tn == 0 and tn < 4096


def test_expert_gmm_dispatch_and_gradient(monkeypatch):
    """`ops.expert_gmm` counts its path; on the kernel path its gradient is
    ragged_dot's."""
    monkeypatch.setattr(ops, "TAKEN", type(ops.TAKEN)())
    ks = jax.random.split(jax.random.key(9), 2)
    x, w = jax.random.normal(ks[0], (32, 16)), jax.random.normal(ks[1], (4, 16, 8))
    sizes = jnp.asarray([8, 0, 16, 8], jnp.int32)
    loss = lambda f: lambda x, w: jnp.sum(f(x, w, sizes) ** 2)
    want = jax.grad(loss(jax.lax.ragged_dot), argnums=(0, 1))(x, w)
    assert rel_err(ops.expert_gmm(x, w, sizes), jax.lax.ragged_dot(x, w, sizes)) == 0.0
    monkeypatch.setattr(ops, "_mode", lambda: "interpret")
    got = jax.grad(loss(ops.expert_gmm), argnums=(0, 1))(x, w)
    for g, e in zip(got, want):
        assert rel_err(g, e) < TOL[jnp.float32]
    assert dict(ops.TAKEN) == {("expert_gmm", "ref"): 1, ("expert_gmm", "interpret"): 1}


@pytest.mark.parametrize("mode", ["ref", "interpret"])
def test_expert_gmm_reads_one_layer_of_a_stack(monkeypatch, mode):
    """With ``layer``, the product is with that layer's experts of the
    (L, E, k, n) stack, on either path."""
    monkeypatch.setattr(ops, "_mode", lambda: mode)
    ks = jax.random.split(jax.random.key(4), 2)
    x, w = jax.random.normal(ks[0], (40, 16)), jax.random.normal(ks[1], (3, 4, 16, 8))
    sizes = jnp.asarray([16, 0, 8, 16], jnp.int32)
    for layer in range(3):
        got = jax.jit(lambda x, w, i: ops.expert_gmm(x, w, sizes, layer=i))(x, w, layer)
        assert rel_err(got, jax.lax.ragged_dot(x, w[layer], sizes)) < TOL[jnp.float32]
