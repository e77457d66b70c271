"""Compiled vs interpret parity for every Pallas kernel in the repo.

Each kernel family (flash_attention, ssd_scan, rglru_scan) is run twice
on identical inputs — once with ``interpret=True`` and once with
``interpret=False`` — and the outputs must match bit-for-bit.  On backends where compiled Pallas is not
available (CPU raises ``ValueError: Only interpret mode is supported on
CPU backend.``), the parity half SKIPS with the refusal recorded in the
skip reason, so a CI log always shows *why* compiled mode wasn't proven.

The module also pins the backend-aware ``interpret`` defaults
(``kernels/interpret.py``): every public kernel entry point now takes
``interpret: bool | None = None`` and resolves ``None`` to interpreter
mode exactly when the default backend is CPU — calling a kernel with no
``interpret`` argument must never crash on the shipped backend.
"""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.kernels.interpret import default_interpret, resolve_interpret


def _compiled(label, fn, *args, **kwargs):
    """Run ``fn`` with interpret=False; skip (recording the backend's
    refusal) where compiled Pallas is unsupported."""
    try:
        return fn(*args, interpret=False, **kwargs)
    except ValueError as e:
        if "interpret mode" in str(e):
            pytest.skip(f"{label}: compiled Pallas unavailable on "
                        f"backend={jax.default_backend()!r}: {e}")
        raise


# --- the backend-aware default ------------------------------------------------
def test_default_interpret_tracks_backend():
    assert default_interpret() == (jax.default_backend() == "cpu")
    assert resolve_interpret(None) == default_interpret()
    assert resolve_interpret(True) is True
    assert resolve_interpret(False) is False


def test_kernels_run_with_no_interpret_argument():
    """Every public entry point works with the resolved default — no
    caller may need to know the backend to call a kernel."""
    from repro.kernels.flash_attention.ops import flash_attention
    from repro.kernels.rglru_scan.ops import rglru_scan
    from repro.kernels.ssd_scan.ops import ssd_scan

    rng = np.random.default_rng(0)
    q = jnp.asarray(rng.standard_normal((1, 8, 2, 16)), jnp.float32)
    kv = jnp.asarray(rng.standard_normal((1, 8, 2, 16)), jnp.float32)
    assert flash_attention(q, kv, kv, block_q=8, block_k=8).shape == q.shape

    a = jnp.full((1, 16, 8), 0.5, jnp.float32)
    b = jnp.ones((1, 16, 8), jnp.float32)
    assert rglru_scan(a, b, chunk=16).shape == a.shape

    x = jnp.asarray(rng.standard_normal((1, 16, 2, 8)), jnp.float32)
    dt = jnp.full((1, 16, 2), 0.1, jnp.float32)
    B = jnp.asarray(rng.standard_normal((1, 16, 4)), jnp.float32)
    A = jnp.full((2,), -1.0, jnp.float32)
    assert ssd_scan(x, dt, B, B, A, chunk=16).shape == x.shape


# --- per-kernel compiled/interpret parity ------------------------------------
def test_flash_attention_parity():
    from repro.kernels.flash_attention.ops import flash_attention
    rng = np.random.default_rng(6)
    q = jnp.asarray(rng.standard_normal((2, 24, 4, 16)), jnp.float32)
    kv = jnp.asarray(rng.standard_normal((2, 24, 2, 16)), jnp.float32)
    kw = dict(causal=True, block_q=8, block_k=8)
    interp = np.asarray(flash_attention(q, kv, kv, interpret=True, **kw))
    comp = np.asarray(_compiled("flash_attention", flash_attention,
                                q, kv, kv, **kw))
    np.testing.assert_array_equal(comp, interp)


def test_ssd_scan_parity():
    from repro.kernels.ssd_scan.ops import ssd_scan
    rng = np.random.default_rng(7)
    x = jnp.asarray(rng.standard_normal((2, 32, 2, 8)), jnp.float32)
    dt = jnp.asarray(rng.uniform(0.01, 0.2, (2, 32, 2)), jnp.float32)
    B = jnp.asarray(rng.standard_normal((2, 32, 4)), jnp.float32)
    C = jnp.asarray(rng.standard_normal((2, 32, 4)), jnp.float32)
    A = jnp.asarray(rng.uniform(-2.0, -0.5, 2), jnp.float32)
    interp = np.asarray(ssd_scan(x, dt, B, C, A, chunk=16, interpret=True))
    comp = np.asarray(_compiled("ssd_scan", ssd_scan, x, dt, B, C, A,
                                chunk=16))
    np.testing.assert_array_equal(comp, interp)


def test_rglru_scan_parity():
    from repro.kernels.rglru_scan.ops import rglru_scan
    rng = np.random.default_rng(8)
    a = jnp.asarray(rng.uniform(0.2, 0.99, (2, 32, 8)), jnp.float32)
    b = jnp.asarray(rng.standard_normal((2, 32, 8)), jnp.float32)
    interp = np.asarray(rglru_scan(a, b, chunk=16, interpret=True))
    comp = np.asarray(_compiled("rglru_scan", rglru_scan, a, b, chunk=16))
    np.testing.assert_array_equal(comp, interp)
