"""Per-kernel validation: Pallas (interpret mode on CPU; TPU is the
target) vs the pure-jnp oracle in ref.py, across shape/dtype sweeps."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.kernels.flash_attention import ops as fa_ops, ref as fa_ref
from repro.kernels.fused_matmul import ops as fm_ops, ref as fm_ref
from repro.kernels.linear_scan import ops as ls_ops, ref as ls_ref


# ---------------------------------------------------------------------------
# fused matmul
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("m,k,n", [(128, 128, 128), (256, 512, 192),
                                   (64, 96, 32), (200, 100, 300)])
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_fused_matmul_shapes(m, k, n, dtype):
    key = jax.random.PRNGKey(m * 7 + k * 3 + n)
    x = jax.random.normal(key, (m, k)).astype(dtype)
    w = jax.random.normal(jax.random.fold_in(key, 1), (k, n)).astype(dtype)
    y = fm_ops.fused_matmul(x, w, epilogue=[],
                            tile={"bm": 128, "bn": 128, "bk": 128},
                            out_dtype=str(jnp.dtype(dtype)), interpret=True)
    ref = fm_ref.fused_matmul_ref(x, w, out_dtype=str(jnp.dtype(dtype)))
    tol = 2e-2 if dtype == jnp.bfloat16 else 2e-4
    np.testing.assert_allclose(np.asarray(y, np.float32),
                               np.asarray(ref, np.float32),
                               rtol=tol, atol=tol)


@pytest.mark.parametrize("epi", [
    [("add", "bias", {})],
    [("add", "bias", {}), ("relu", None, {})],
    [("add", "bias", {}), ("silu", None, {}), ("add", "res", {})],
])
def test_fused_matmul_epilogues(epi):
    key = jax.random.PRNGKey(0)
    m, k, n = 128, 64, 128
    x = jax.random.normal(key, (m, k))
    w = jax.random.normal(jax.random.fold_in(key, 1), (k, n))
    bias = jax.random.normal(jax.random.fold_in(key, 2), (n,))
    res = jax.random.normal(jax.random.fold_in(key, 3), (m, n))
    epi_args = []
    ref = x @ w
    for fn, arg, at in epi:
        v = {"bias": bias, "res": res, None: None}[arg]
        epi_args.append((fn, [v] if v is not None else [], at))
        if fn == "add":
            ref = ref + v
        elif fn == "relu":
            ref = jax.nn.relu(ref)
        elif fn == "silu":
            ref = jax.nn.silu(ref)
    y = fm_ops.fused_matmul(x, w, epilogue=epi_args,
                            tile={"bm": 64, "bn": 64, "bk": 64},
                            out_dtype="float32", interpret=True)
    np.testing.assert_allclose(y, ref, rtol=2e-5, atol=2e-5)


@pytest.mark.parametrize("kind", ["row", "full"])
def test_fused_matmul_row_tile_bitwise(kind):
    """A [slots, 1, d] decode activation in one row block (bm = all rows,
    the weight streamed once) gives the bits of 8-row blocks: the k-order
    of every accumulation is the same."""
    key = jax.random.PRNGKey(96)
    slots, k, n = 96, 256, 384
    x = jax.random.normal(key, (slots, 1, k)).astype(jnp.bfloat16)
    w = jax.random.normal(jax.random.fold_in(key, 1), (k, n)).astype(
        jnp.bfloat16)
    shape = (n,) if kind == "row" else (slots, 1, n)
    v = jax.random.normal(jax.random.fold_in(key, 2), shape).astype(
        jnp.bfloat16)
    epi = [("add", [v], {}), ("silu", [], {})]

    def run(bm):
        return np.asarray(fm_ops.fused_matmul(
            x, w, epilogue=epi, tile={"bm": bm, "bn": 128, "bk": 128},
            out_dtype="bfloat16", interpret=True), np.float32)

    one = run(slots)
    assert one.shape == (slots, 1, n)
    assert fm_ops.row_block(slots, slots) == slots
    assert fm_ops.row_block(1, slots) == 8
    np.testing.assert_array_equal(one, run(8))


# ---------------------------------------------------------------------------
# flash attention
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("sq,skv,h,hkv,d,causal", [
    (128, 128, 4, 4, 64, True),
    (128, 128, 4, 2, 64, False),
    (256, 256, 2, 1, 32, True),
    (64, 192, 2, 2, 64, False),     # cross attention (kv longer)
    (100, 100, 3, 1, 48, True),     # ragged, non-128 shapes
])
def test_flash_attention_sweep(sq, skv, h, hkv, d, causal):
    key = jax.random.PRNGKey(sq + skv + h)
    q = jax.random.normal(key, (2, sq, h, d))
    k = jax.random.normal(jax.random.fold_in(key, 1), (2, skv, hkv, d))
    v = jax.random.normal(jax.random.fold_in(key, 2), (2, skv, hkv, d))
    ref = fa_ref.attention_ref(q, k, v, causal=causal)
    out = fa_ops.flash_attention(q, k, v, causal=causal,
                                 block_q=64, block_kv=64, interpret=True)
    np.testing.assert_allclose(out, ref, rtol=2e-4, atol=2e-4)


@pytest.mark.parametrize("dtype", [jnp.bfloat16, jnp.float32])
def test_flash_attention_dtypes(dtype):
    key = jax.random.PRNGKey(9)
    q = jax.random.normal(key, (1, 128, 2, 64)).astype(dtype)
    k = jax.random.normal(jax.random.fold_in(key, 1), (1, 128, 2, 64)).astype(dtype)
    v = jax.random.normal(jax.random.fold_in(key, 2), (1, 128, 2, 64)).astype(dtype)
    ref = fa_ref.attention_ref(q, k, v, causal=True)
    out = fa_ops.flash_attention(q, k, v, causal=True, block_q=64,
                                 block_kv=64, interpret=True)
    tol = 2e-2 if dtype == jnp.bfloat16 else 2e-4
    np.testing.assert_allclose(np.asarray(out, np.float32),
                               np.asarray(ref, np.float32),
                               rtol=tol, atol=tol)


def test_flash_attention_jnp_blockwise_matches():
    key = jax.random.PRNGKey(10)
    q = jax.random.normal(key, (2, 256, 2, 32))
    k = jax.random.normal(jax.random.fold_in(key, 1), (2, 256, 2, 32))
    v = jax.random.normal(jax.random.fold_in(key, 2), (2, 256, 2, 32))
    ref = fa_ref.attention_ref(q, k, v, causal=True)
    out = fa_ops.flash_attention_jnp(q, k, v, causal=True, block_kv=64)
    np.testing.assert_allclose(out, ref, rtol=2e-4, atol=2e-4)


def test_flash_attention_grad_matches_ref():
    key = jax.random.PRNGKey(11)
    q = jax.random.normal(key, (1, 64, 2, 32))
    k = jax.random.normal(jax.random.fold_in(key, 1), (1, 64, 2, 32))
    v = jax.random.normal(jax.random.fold_in(key, 2), (1, 64, 2, 32))

    def loss_k(q, k, v):
        return jnp.sum(fa_ops.flash_attention_vjp(
            q, k, v, True, 32, 32, True) ** 2)

    def loss_r(q, k, v):
        return jnp.sum(fa_ref.attention_ref(q, k, v, causal=True) ** 2)

    gk = jax.grad(loss_k, argnums=(0, 1, 2))(q, k, v)
    gr = jax.grad(loss_r, argnums=(0, 1, 2))(q, k, v)
    for a, b in zip(gk, gr):
        np.testing.assert_allclose(a, b, rtol=5e-3, atol=5e-3)


# ---------------------------------------------------------------------------
# linear scan (RWKV6 / GLA / Mamba2-SSD)
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("s,dk,dv,chunk", [
    (64, 32, 32, 16), (37, 16, 48, 16), (128, 64, 64, 8), (16, 8, 8, 16),
])
@pytest.mark.parametrize("rwkv", [False, True])
def test_linear_scan_kernel_sweep(s, dk, dv, chunk, rwkv):
    key = jax.random.PRNGKey(s * 10 + dk)
    B, H = 2, 2
    q = jax.random.normal(key, (B, s, H, dk))
    k = jax.random.normal(jax.random.fold_in(key, 1), (B, s, H, dk))
    v = jax.random.normal(jax.random.fold_in(key, 2), (B, s, H, dv))
    w = jnp.exp(jax.random.uniform(jax.random.fold_in(key, 3),
                                   (B, s, H, dk), minval=-7.3, maxval=-1e-3))
    u = jax.random.normal(jax.random.fold_in(key, 4), (H, dk)) if rwkv \
        else None
    ref = ls_ref.linear_scan_ref(q, k, v, w, u=u)
    out = ls_ops.linear_scan(q, k, v, w, u=u, chunk=chunk, interpret=True)
    np.testing.assert_allclose(out, ref, rtol=5e-3, atol=5e-3)


def test_linear_scan_state_carry():
    """Chunked scan with init_state+return_state == one long scan."""
    key = jax.random.PRNGKey(5)
    B, S, H, D = 1, 64, 2, 16
    q = jax.random.normal(key, (B, S, H, D))
    k = jax.random.normal(jax.random.fold_in(key, 1), (B, S, H, D))
    v = jax.random.normal(jax.random.fold_in(key, 2), (B, S, H, D))
    w = jnp.exp(jax.random.uniform(jax.random.fold_in(key, 3),
                                   (B, S, H, D), minval=-2.0, maxval=-1e-3))
    u = jnp.abs(jax.random.normal(jax.random.fold_in(key, 4), (H, D)))
    full = ls_ref.linear_scan_ref(q, k, v, w, u=u)
    half = S // 2
    o1, st = ls_ops.linear_scan_chunked(q[:, :half], k[:, :half],
                                        v[:, :half], w[:, :half], u=u,
                                        return_state=True)
    o2, _ = ls_ops.linear_scan_chunked(q[:, half:], k[:, half:], v[:, half:],
                                       w[:, half:], u=u, init_state=st,
                                       return_state=True)
    np.testing.assert_allclose(jnp.concatenate([o1, o2], axis=1), full,
                               rtol=2e-3, atol=2e-3)


def test_linear_scan_grad_path():
    key = jax.random.PRNGKey(6)
    B, S, H, D = 1, 32, 1, 8
    q = jax.random.normal(key, (B, S, H, D))
    k = jax.random.normal(jax.random.fold_in(key, 1), (B, S, H, D))
    v = jax.random.normal(jax.random.fold_in(key, 2), (B, S, H, D))
    w = jnp.exp(jax.random.uniform(jax.random.fold_in(key, 3),
                                   (B, S, H, D), minval=-2.0, maxval=-1e-3))

    g1 = jax.grad(lambda q: jnp.sum(
        ls_ops.linear_scan_chunked(q, k, v, w) ** 2))(q)
    g2 = jax.grad(lambda q: jnp.sum(
        ls_ref.linear_scan_ref(q, k, v, w) ** 2))(q)
    np.testing.assert_allclose(g1, g2, rtol=2e-3, atol=2e-3)


def test_fused_matmul_vjp_grad_matches_ref():
    """The custom-VJP form the lowering binds for ``fused_kernel``: unary and
    operand epilogue stages, gradients w.r.t. x, w and the operands."""
    key = jax.random.PRNGKey(12)
    x = jax.random.normal(key, (64, 96))
    w = jax.random.normal(jax.random.fold_in(key, 1), (96, 128))
    b = jax.random.normal(jax.random.fold_in(key, 2), (128,))
    res = jax.random.normal(jax.random.fold_in(key, 3), (64, 128))
    stages = (("add", 1, ()), ("silu", 0, ()), ("add", 1, ()))
    tile = (("bm", 64), ("bn", 128), ("bk", 128))

    def loss_k(x, w, b, res):
        return jnp.sum(fm_ops.fused_matmul_vjp(
            x, w, (b, res), stages, "float32", tile, True) ** 2)

    def loss_r(x, w, b, res):
        y = jax.nn.silu(x @ w + b) + res
        return jnp.sum(y ** 2)

    gk = jax.grad(loss_k, argnums=(0, 1, 2, 3))(x, w, b, res)
    gr = jax.grad(loss_r, argnums=(0, 1, 2, 3))(x, w, b, res)
    for a, r in zip(gk, gr):
        np.testing.assert_allclose(a, r, rtol=2e-4, atol=2e-4)


@pytest.mark.parametrize("rwkv", [False, True])
def test_linear_scan_vjp_grad_matches_ref(rwkv):
    key = jax.random.PRNGKey(13)
    B, S, H, D = 1, 32, 2, 8
    q, k, v = (jax.random.normal(jax.random.fold_in(key, i), (B, S, H, D))
               for i in range(3))
    w = jnp.exp(jax.random.uniform(jax.random.fold_in(key, 3),
                                   (B, S, H, D), minval=-2.0, maxval=-1e-3))
    u = jax.random.normal(jax.random.fold_in(key, 4), (H, D)) if rwkv \
        else None
    gk = jax.grad(lambda q: jnp.sum(
        ls_ops.linear_scan_vjp(q, k, v, w, u, 16, True) ** 2))(q)
    gr = jax.grad(lambda q: jnp.sum(
        ls_ref.linear_scan_ref(q, k, v, w, u=u) ** 2))(q)
    np.testing.assert_allclose(gk, gr, rtol=2e-3, atol=2e-3)
