"""Per-kernel interpret-mode sweeps against the pure-jnp oracles."""
import jax.numpy as jnp
import numpy as np
import pytest

RNG = np.random.default_rng(42)


# --- flash attention -------------------------------------------------------
@pytest.mark.parametrize("b,sq,sk,hq,hkv,d,causal,window,dtype,tol", [
    (2, 256, 256, 4, 2, 64, True, None, jnp.float32, 2e-5),
    (1, 200, 200, 4, 1, 64, True, 64, jnp.float32, 2e-5),
    (2, 128, 384, 8, 8, 128, False, None, jnp.float32, 2e-5),
    (1, 256, 256, 2, 2, 64, True, None, jnp.bfloat16, 2e-2),
    (1, 96, 96, 4, 4, 32, True, 32, jnp.float32, 2e-5),
])
def test_flash_attention_sweep(b, sq, sk, hq, hkv, d, causal, window,
                               dtype, tol):
    from repro.kernels.flash_attention.ops import flash_attention
    from repro.kernels.flash_attention.ref import attention_ref
    q = jnp.asarray(RNG.standard_normal((b, sq, hq, d)), dtype)
    k = jnp.asarray(RNG.standard_normal((b, sk, hkv, d)), dtype)
    v = jnp.asarray(RNG.standard_normal((b, sk, hkv, d)), dtype)
    got = flash_attention(q, k, v, causal=causal, window=window)
    want = attention_ref(q.transpose(0, 2, 1, 3), k.transpose(0, 2, 1, 3),
                         v.transpose(0, 2, 1, 3), causal=causal,
                         window=window).transpose(0, 2, 1, 3)
    err = float(jnp.max(jnp.abs(got.astype(jnp.float32)
                                - want.astype(jnp.float32))))
    assert err < tol, err


# --- ssd scan ----------------------------------------------------------------
@pytest.mark.parametrize("b,s,h,hd,n,chunk,dtype,tol", [
    (2, 256, 3, 32, 16, 128, jnp.float32, 1e-4),
    (1, 384, 2, 64, 128, 128, jnp.float32, 1e-4),
    (1, 256, 2, 32, 64, 64, jnp.float32, 1e-4),
    (1, 256, 2, 32, 16, 128, jnp.bfloat16, 5e-2),
])
def test_ssd_scan_sweep(b, s, h, hd, n, chunk, dtype, tol):
    from repro.kernels.ssd_scan.ops import ssd_scan
    from repro.kernels.ssd_scan.ref import ssd_ref
    x = jnp.asarray(RNG.standard_normal((b, s, h, hd)), dtype)
    dt = jnp.asarray(RNG.uniform(0.001, 0.1, (b, s, h)), jnp.float32)
    B = jnp.asarray(RNG.standard_normal((b, s, n)) * 0.3, dtype)
    C = jnp.asarray(RNG.standard_normal((b, s, n)) * 0.3, dtype)
    A = jnp.asarray(-np.exp(RNG.uniform(-1, 1, (h,))), jnp.float32)
    got = ssd_scan(x, dt, B, C, A, chunk=chunk)
    xr = x.transpose(0, 2, 1, 3).reshape(b * h, s, hd)
    dtr = dt.transpose(0, 2, 1).reshape(b * h, s, 1)
    Br = jnp.broadcast_to(B[:, None], (b, h, s, n)).reshape(b * h, s, n)
    Cr = jnp.broadcast_to(C[:, None], (b, h, s, n)).reshape(b * h, s, n)
    Ar = jnp.broadcast_to(A[None, :], (b, h)).reshape(b * h, 1)
    want = ssd_ref(xr, dtr, Br, Cr, Ar).reshape(b, h, s, hd
                                                ).transpose(0, 2, 1, 3)
    rel = (float(jnp.max(jnp.abs(got.astype(jnp.float32)
                                 - want.astype(jnp.float32))))
           / (float(jnp.max(jnp.abs(want.astype(jnp.float32)))) + 1e-9))
    assert rel < tol, rel


# --- rglru scan --------------------------------------------------------------
@pytest.mark.parametrize("b,s,w,chunk,dtype,tol", [
    (2, 200, 128, 128, jnp.float32, 1e-5),
    (1, 512, 256, 128, jnp.float32, 1e-5),
    (1, 130, 128, 64, jnp.bfloat16, 2e-2),
])
def test_rglru_scan_sweep(b, s, w, chunk, dtype, tol):
    from repro.kernels.rglru_scan.ops import rglru_scan
    from repro.kernels.rglru_scan.ref import rglru_ref
    a = jnp.asarray(RNG.uniform(0.7, 0.999, (b, s, w)), dtype)
    bb = jnp.asarray(RNG.standard_normal((b, s, w)) * 0.1, dtype)
    got = rglru_scan(a, bb, chunk=chunk)
    want = rglru_ref(a, bb)
    err = float(jnp.max(jnp.abs(got.astype(jnp.float32)
                                - want.astype(jnp.float32))))
    assert err < tol, err


# --- windowed attention (XLA twin of the kernel's block skipping) -------------
@pytest.mark.parametrize("s,window,heads,kv", [(700, 37, 4, 2),
                                               (2048, 256, 4, 4),
                                               (513, 100, 2, 1)])
def test_windowed_attention_matches_dense(s, window, heads, kv, mesh1=None):
    import jax
    import jax.numpy as jnp
    from repro.models.attention import Attention, AttentionConfig, _mask
    cfg = AttentionConfig(d_model=64, n_heads=heads, n_kv=kv, head_dim=16,
                          window=window, causal=True)
    attn = Attention(cfg)
    p = attn.init(jax.random.PRNGKey(0))
    x = jnp.asarray(RNG.standard_normal((2, s, 64)), jnp.float32)
    pos = jnp.arange(s)[None].repeat(2, 0)
    q, k, v = attn._qkv(p, x, None, pos, pos)
    dense = attn._attend_dense(q, k, v, _mask(pos[0], pos[0], cfg))
    wind = attn._attend_windowed(q, k, v, pos[0], pos[0])
    err = float(jnp.max(jnp.abs(dense - wind)))
    assert err < 2e-5, err
