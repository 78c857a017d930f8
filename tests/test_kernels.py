"""Pallas kernels vs pure-jnp oracles: shape/dtype sweeps, interpret mode."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.kernels import ref
from repro.kernels.decode_attention import decode_attention
from repro.kernels.grouped_gemm import grouped_gemm
from repro.kernels.hydro_rhs import (
    hydro_flux_pallas, hydro_reconstruct_pallas, hydro_rhs_pallas,
)

KW = dict(h=0.01, gamma=1.4, ghost=3, subgrid=8)


def _random_state(key, n, s=8, g=3, dtype=jnp.float32):
    p = s + 2 * g
    k1, k2, k3 = jax.random.split(key, 3)
    rho = 1.0 + 0.3 * jax.random.uniform(k1, (n, 1, p, p, p), dtype)
    v = 0.2 * jax.random.normal(k2, (n, 3, p, p, p), dtype)
    pr = 1.0 + 0.5 * jax.random.uniform(k3, (n, 1, p, p, p), dtype)
    e = pr / 0.4 + 0.5 * rho * jnp.sum(v * v, axis=1, keepdims=True)
    return jnp.concatenate([rho, rho * v, e], axis=1)


# ---------------------------------------------------------------------------
# hydro kernels
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("layout", ["slot_grid", "slot_lane"])
@pytest.mark.parametrize("n_slots", [1, 4, 8])
def test_hydro_rhs_kernel_matches_oracle(layout, n_slots):
    u = _random_state(jax.random.PRNGKey(n_slots), n_slots)
    out = hydro_rhs_pallas(u, layout=layout, **KW)
    want = ref.hydro_rhs_ref(u, **KW)
    scale = float(jnp.max(jnp.abs(want)))
    np.testing.assert_allclose(np.asarray(out), np.asarray(want),
                               atol=2e-6 * max(scale, 1.0), rtol=2e-5)


@pytest.mark.parametrize("subgrid,ghost", [(4, 3), (8, 3), (16, 3)])
def test_hydro_rhs_kernel_shape_sweep(subgrid, ghost):
    """S1 knob sweep: the kernel handles any sub-grid size."""
    kw = dict(h=0.01, gamma=1.4, ghost=ghost, subgrid=subgrid)
    u = _random_state(jax.random.PRNGKey(0), 2, s=subgrid, g=ghost)
    out = hydro_rhs_pallas(u, **kw)
    want = ref.hydro_rhs_ref(u, **kw)
    assert out.shape == (2, 5, subgrid, subgrid, subgrid)
    scale = float(jnp.max(jnp.abs(want)))
    np.testing.assert_allclose(np.asarray(out), np.asarray(want),
                               atol=2e-6 * max(scale, 1.0), rtol=2e-5)


@pytest.mark.parametrize("layout", ["slot_grid", "slot_lane"])
def test_hydro_rhs_kernel_traced_h(layout):
    """Per-slot traced h: a mixed-width batch is bit-identical to the same
    kernel run per width group, and allclose to the static-h program."""
    u = _random_state(jax.random.PRNGKey(7), 8)
    kw = dict(gamma=1.4, ghost=3, subgrid=8)
    # widths ALTERNATE so every lane tile is width-heterogeneous (a kernel
    # that collapsed h to one scalar per block would fail, not pass)
    hs = jnp.where(jnp.arange(8) % 2 == 0, 0.02, 0.01).astype(u.dtype)
    mixed = hydro_rhs_pallas(u, h_slots=hs, layout=layout, **kw)
    for i in range(8):
        one = hydro_rhs_pallas(u[i:i + 1], h_slots=hs[i:i + 1],
                               layout=layout, **kw)
        np.testing.assert_array_equal(np.asarray(mixed[i:i + 1]),
                                      np.asarray(one))
    static = hydro_rhs_pallas(u, h=0.01, layout=layout, **kw)
    scale = float(jnp.max(jnp.abs(static)))
    np.testing.assert_allclose(np.asarray(mixed[1::2]),
                               np.asarray(static[1::2]),
                               atol=2e-5 * max(scale, 1.0), rtol=2e-5)


def test_hydro_split_kernels_match_fused():
    """Paper-faithful two-kernel structure == fused kernel == oracle."""
    u = _random_state(jax.random.PRNGKey(7), 4)
    recon = hydro_reconstruct_pallas(u)
    np.testing.assert_allclose(np.asarray(recon),
                               np.asarray(ref.hydro_reconstruct_ref(u)),
                               rtol=1e-5, atol=1e-5)
    flux = hydro_flux_pallas(recon, **KW)
    fused = hydro_rhs_pallas(u, **KW)
    scale = float(jnp.max(jnp.abs(flux)))
    np.testing.assert_allclose(np.asarray(flux), np.asarray(fused),
                               atol=3e-6 * max(scale, 1.0), rtol=2e-5)


# ---------------------------------------------------------------------------
# grouped GEMM
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
@pytest.mark.parametrize("e,c,k,n", [(4, 256, 512, 384), (2, 128, 256, 128),
                                     (8, 128, 128, 256)])
def test_grouped_gemm_sweep(dtype, e, c, k, n):
    key = jax.random.PRNGKey(e * 100 + n)
    ks = jax.random.split(key, 3)
    x = (jax.random.normal(ks[0], (e, c, k)) * 0.1).astype(dtype)
    w = (jax.random.normal(ks[1], (e, k, n)) * 0.1).astype(dtype)
    gl = jax.random.randint(ks[2], (e,), 0, c + 1)
    y = grouped_gemm(x, w, gl, bc=128, bn=128, bk=128)
    want = ref.grouped_gemm_ref(x, w, gl)
    tol = 1e-5 if dtype == jnp.float32 else 2e-2
    np.testing.assert_allclose(np.asarray(y, np.float32),
                               np.asarray(want, np.float32),
                               atol=tol, rtol=tol)


def test_grouped_gemm_empty_and_full_groups():
    e, c, k, n = 3, 128, 128, 128
    x = jnp.ones((e, c, k), jnp.float32)
    w = jnp.ones((e, k, n), jnp.float32)
    gl = jnp.array([0, c, 17], jnp.int32)
    y = grouped_gemm(x, w, gl)
    assert float(jnp.max(jnp.abs(y[0]))) == 0.0          # empty group -> 0
    np.testing.assert_allclose(np.asarray(y[1]), float(k))
    assert float(jnp.max(jnp.abs(y[2, 17:]))) == 0.0     # beyond group -> 0
    np.testing.assert_allclose(np.asarray(y[2, :17]), float(k))


# ---------------------------------------------------------------------------
# decode attention
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("hq,hkv", [(8, 2), (4, 4), (12, 4)])
@pytest.mark.parametrize("s,bs", [(512, 128), (1024, 512)])
def test_decode_attention_sweep(hq, hkv, s, bs):
    b, d = 3, 64
    key = jax.random.PRNGKey(hq * s)
    ks = jax.random.split(key, 4)
    q = jax.random.normal(ks[0], (b, hq, d))
    kc = jax.random.normal(ks[1], (b, s, hkv, d))
    vc = jax.random.normal(ks[2], (b, s, hkv, d))
    cl = jax.random.randint(ks[3], (b,), 1, s + 1)
    o = decode_attention(q, kc, vc, cl, bs=bs)
    want = ref.decode_attention_ref(q, kc, vc, cl)
    np.testing.assert_allclose(np.asarray(o), np.asarray(want),
                               atol=2e-5, rtol=2e-5)


def test_decode_attention_ragged_lengths():
    """Aggregated requests of very different lengths stay independent."""
    b, hq, hkv, d, s = 4, 4, 2, 32, 512
    key = jax.random.PRNGKey(0)
    ks = jax.random.split(key, 3)
    q = jax.random.normal(ks[0], (b, hq, d))
    kc = jax.random.normal(ks[1], (b, s, hkv, d))
    vc = jax.random.normal(ks[2], (b, s, hkv, d))
    cl = jnp.array([1, 100, 333, 512], jnp.int32)
    batched = decode_attention(q, kc, vc, cl, bs=128)
    for i in range(b):
        solo = decode_attention(q[i:i + 1], kc[i:i + 1], vc[i:i + 1],
                                cl[i:i + 1], bs=128)
        np.testing.assert_allclose(np.asarray(batched[i]),
                                   np.asarray(solo[0]), atol=2e-5, rtol=2e-5)


def test_decode_attention_bf16():
    b, hq, hkv, d, s = 2, 4, 2, 64, 256
    key = jax.random.PRNGKey(1)
    ks = jax.random.split(key, 3)
    q = jax.random.normal(ks[0], (b, hq, d)).astype(jnp.bfloat16)
    kc = jax.random.normal(ks[1], (b, s, hkv, d)).astype(jnp.bfloat16)
    vc = jax.random.normal(ks[2], (b, s, hkv, d)).astype(jnp.bfloat16)
    cl = jnp.array([256, 33], jnp.int32)
    o = decode_attention(q, kc, vc, cl, bs=128)
    want = ref.decode_attention_ref(q, kc, vc, cl)
    np.testing.assert_allclose(np.asarray(o, np.float32),
                               np.asarray(want, np.float32),
                               atol=2e-2, rtol=2e-2)
