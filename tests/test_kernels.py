"""Per-kernel shape/dtype sweeps: Pallas (interpret mode) vs jnp oracles."""

import jax.numpy as jnp
import numpy as np
import pytest

from repro.kernels.expert_gmm.ops import expert_gmm
from repro.kernels.expert_gmm.ref import expert_gmm_ref
from repro.kernels.flash_attention.ops import flash_attention
from repro.kernels.flash_attention.ref import flash_attention_ref
from repro.kernels.int8_matmul.ops import int8_matmul, quantized_matmul
from repro.kernels.int8_matmul.ref import int8_matmul_ref, quantize_matmul_ref
from repro.kernels.mla_decode.ops import mla_decode
from repro.kernels.mla_decode.ref import mla_decode_ref
from repro.kernels.ssm_scan.ops import ssm_scan
from repro.kernels.ssm_scan.ref import ssm_scan_ref
from repro.kernels.tanh_lut.ops import make_lut, tanh_lut
from repro.kernels.tanh_lut.ref import tanh_lut_ref

RNG = np.random.default_rng(7)


# ---------------------------------------------------------------------------
# ssm_scan
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("Bsz,T,D,N", [(1, 32, 8, 4), (2, 64, 32, 8),
                                       (1, 128, 64, 16), (3, 96, 24, 4)])
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_ssm_scan_shapes(Bsz, T, D, N, dtype):
    x = jnp.asarray(RNG.normal(size=(Bsz, T, D)), dtype)
    delta = jnp.asarray(RNG.uniform(0.001, 0.8, size=(Bsz, T, D)), dtype)
    A = -jnp.exp(jnp.asarray(RNG.normal(size=(D, N)), jnp.float32))
    B = jnp.asarray(RNG.normal(size=(Bsz, T, N)), dtype)
    C = jnp.asarray(RNG.normal(size=(Bsz, T, N)), dtype)
    y_k, h_k = ssm_scan(x, delta, A, B, C, chunk=32, block_d=16, w=8)
    y_r, h_r = ssm_scan_ref(x, delta, A, B, C, jnp.zeros((Bsz, D, N)))
    tol = 2e-5 if dtype == jnp.float32 else 3e-2
    np.testing.assert_allclose(np.asarray(y_k, np.float32), y_r, atol=tol, rtol=tol)
    np.testing.assert_allclose(h_k, h_r, atol=tol, rtol=tol)


@pytest.mark.parametrize("chunk,block_d,w", [(16, 8, 4), (32, 32, 8), (64, 16, 16)])
def test_ssm_scan_blocking_invariance(chunk, block_d, w):
    """BlockSpec tiling choices must not change the math (j-step property)."""
    Bsz, T, D, N = 2, 64, 32, 8
    x = jnp.asarray(RNG.normal(size=(Bsz, T, D)), jnp.float32)
    delta = jnp.asarray(RNG.uniform(0.001, 0.5, size=(Bsz, T, D)), jnp.float32)
    A = -jnp.exp(jnp.asarray(RNG.normal(size=(D, N)), jnp.float32))
    B = jnp.asarray(RNG.normal(size=(Bsz, T, N)), jnp.float32)
    C = jnp.asarray(RNG.normal(size=(Bsz, T, N)), jnp.float32)
    y_r, _ = ssm_scan_ref(x, delta, A, B, C, jnp.zeros((Bsz, D, N)))
    y_k, _ = ssm_scan(x, delta, A, B, C, chunk=chunk, block_d=block_d, w=w)
    np.testing.assert_allclose(y_k, y_r, atol=3e-5, rtol=1e-4)


def test_ssm_scan_resume_parity():
    """A nonzero carry must not raise — it falls back to the ref path, so
    chunked prefill (scan first half, resume with h_final) exactly equals
    the one-shot scan."""
    Bsz, T, D, N = 2, 64, 16, 4
    x = jnp.asarray(RNG.normal(size=(Bsz, T, D)), jnp.float32)
    delta = jnp.asarray(RNG.uniform(0.001, 0.5, size=(Bsz, T, D)), jnp.float32)
    A = -jnp.exp(jnp.asarray(RNG.normal(size=(D, N)), jnp.float32))
    B = jnp.asarray(RNG.normal(size=(Bsz, T, N)), jnp.float32)
    C = jnp.asarray(RNG.normal(size=(Bsz, T, N)), jnp.float32)
    y_full, h_full = ssm_scan(x, delta, A, B, C)
    h = T // 2
    y1, h_mid = ssm_scan(x[:, :h], delta[:, :h], A, B[:, :h], C[:, :h])
    y2, h_end = ssm_scan(x[:, h:], delta[:, h:], A, B[:, h:], C[:, h:],
                         h0=h_mid)  # used to raise NotImplementedError
    np.testing.assert_allclose(np.concatenate([y1, y2], axis=1), y_full,
                               atol=3e-5, rtol=1e-4)
    np.testing.assert_allclose(h_end, h_full, atol=3e-5, rtol=1e-4)


def test_ssm_scan_resume_under_jit():
    """Tracing must not crash on the h0 concreteness check: abstract carries
    conservatively take the ref path."""
    import jax

    Bsz, T, D, N = 1, 16, 8, 4
    x = jnp.asarray(RNG.normal(size=(Bsz, T, D)), jnp.float32)
    delta = jnp.asarray(RNG.uniform(0.01, 0.5, size=(Bsz, T, D)), jnp.float32)
    A = -jnp.exp(jnp.asarray(RNG.normal(size=(D, N)), jnp.float32))
    B = jnp.asarray(RNG.normal(size=(Bsz, T, N)), jnp.float32)
    C = jnp.asarray(RNG.normal(size=(Bsz, T, N)), jnp.float32)
    h0 = jnp.asarray(RNG.normal(size=(Bsz, D, N)), jnp.float32)

    y_jit, h_jit = jax.jit(
        lambda h: ssm_scan(x, delta, A, B, C, h0=h))(h0)
    y_ref, h_ref = ssm_scan_ref(x, delta, A, B, C, h0)
    np.testing.assert_allclose(y_jit, y_ref, atol=1e-6)
    np.testing.assert_allclose(h_jit, h_ref, atol=1e-6)


# ---------------------------------------------------------------------------
# flash attention
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("case", [
    dict(B=2, S=64, H=4, KV=2, hd=32, causal=True, window=0, softcap=0.0),
    dict(B=1, S=128, H=8, KV=8, hd=64, causal=True, window=32, softcap=0.0),
    dict(B=2, S=64, H=4, KV=1, hd=16, causal=False, window=0, softcap=0.0),
    dict(B=1, S=96, H=2, KV=2, hd=80, causal=True, window=0, softcap=20.0),
    dict(B=1, S=64, H=9, KV=3, hd=64, causal=True, window=0, softcap=0.0),  # smollm heads
])
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_flash_attention_cases(case, dtype):
    c = dict(case)
    q = jnp.asarray(RNG.normal(size=(c["B"], c["S"], c["H"], c["hd"])), dtype)
    k = jnp.asarray(RNG.normal(size=(c["B"], c["S"], c["KV"], c["hd"])), dtype)
    v = jnp.asarray(RNG.normal(size=(c["B"], c["S"], c["KV"], c["hd"])), dtype)
    o_k = flash_attention(q, k, v, causal=c["causal"], window=c["window"],
                          softcap=c["softcap"], bq=32, bk=32)
    o_r = flash_attention_ref(q, k, v, causal=c["causal"], window=c["window"],
                              softcap=c["softcap"])
    tol = 2e-6 if dtype == jnp.float32 else 2e-2
    np.testing.assert_allclose(
        np.asarray(o_k, np.float32), np.asarray(o_r, np.float32), atol=tol, rtol=tol
    )


def test_flash_attention_matches_model_sdpa():
    """Kernel ≡ the model's _sdpa path (the dry-run fallback)."""
    from repro.models.attention import _sdpa, causal_mask

    q = jnp.asarray(RNG.normal(size=(2, 64, 4, 32)), jnp.float32)
    k = jnp.asarray(RNG.normal(size=(2, 64, 2, 32)), jnp.float32)
    v = jnp.asarray(RNG.normal(size=(2, 64, 2, 32)), jnp.float32)
    o_model = _sdpa(q, k, v, causal_mask(64, 64))
    o_kernel = flash_attention(q, k, v, causal=True, bq=32, bk=32)
    np.testing.assert_allclose(o_model, o_kernel, atol=2e-6)


# ---------------------------------------------------------------------------
# int8 matmul
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("M,K,N", [(32, 64, 16), (64, 128, 32), (128, 256, 128),
                                   (96, 64, 48)])
def test_int8_matmul_bit_exact(M, K, N):
    a_q = jnp.asarray(RNG.integers(-127, 128, size=(M, K)), jnp.int8)
    b_q = jnp.asarray(RNG.integers(-127, 128, size=(K, N)), jnp.int8)
    a_s = jnp.asarray(RNG.uniform(0.01, 0.1, size=(M, 1)), jnp.float32)
    b_s = jnp.asarray(RNG.uniform(0.01, 0.1, size=(1, N)), jnp.float32)
    y_k = int8_matmul(a_q, b_q, a_s, b_s, bm=32, bn=32, bk=32)
    y_r = int8_matmul_ref(a_q, b_q, a_s, b_s)
    np.testing.assert_array_equal(np.asarray(y_k), np.asarray(y_r))


def test_quantized_matmul_accuracy():
    a = jnp.asarray(RNG.normal(size=(64, 128)), jnp.float32)
    b = jnp.asarray(RNG.normal(size=(128, 64)), jnp.float32)
    y_q = quantized_matmul(a, b)
    np.testing.assert_allclose(y_q, quantize_matmul_ref(a, b), atol=1e-5)
    rel = float(jnp.linalg.norm(y_q - a @ b) / jnp.linalg.norm(a @ b))
    assert rel < 0.02  # int8 MACC keeps ~1% relative error on Gaussian data


# ---------------------------------------------------------------------------
# tanh LUT
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("shape", [(256,), (4, 100), (3, 5, 64)])
@pytest.mark.parametrize("addr_bits", [8, 12])
def test_tanh_lut_matches_ref(shape, addr_bits):
    lut = make_lut(addr_bits)
    x = jnp.asarray(RNG.normal(size=shape) * 3, jnp.float32)
    y_k = tanh_lut(x, lut, block=128)
    y_r = tanh_lut_ref(x, lut)
    np.testing.assert_allclose(y_k, y_r, atol=1e-6)
    assert float(jnp.max(jnp.abs(y_r - jnp.tanh(x)))) < 4 ** (1 - addr_bits / 4)


def test_tanh_lut_saturation():
    lut = make_lut(10)
    x = jnp.asarray([-100.0, -4.0, 4.0, 100.0])
    y = tanh_lut(x, lut, block=4)
    np.testing.assert_allclose(y, jnp.tanh(x), atol=2e-3)


# ---------------------------------------------------------------------------
# platform: interpret mode and the scoped-VMEM request
# ---------------------------------------------------------------------------

def test_interpret_mode_follows_platform():
    import jax

    from repro.kernels.platform import resolve_interpret

    assert resolve_interpret() is (jax.default_backend() != "tpu")
    assert resolve_interpret(False) is False
    assert resolve_interpret(True) is True


def test_vmem_limit_counts_tiled_blocks():
    from repro.kernels.platform import (DEFAULT_SCOPED_VMEM, vmem_bytes,
                                        vmem_limit)

    # a [1, 1024] f32 row occupies a full 8-sublane tile; int8 tiles are 32
    assert vmem_bytes((1, 1024), jnp.float32) == 8 * 1024 * 4
    assert vmem_bytes((1, 1024), jnp.int8) == 32 * 1024
    w = vmem_bytes((2048, 4096), jnp.float32)        # paper-lstm gate ROM
    assert w == 32 << 20
    assert vmem_limit(0) == DEFAULT_SCOPED_VMEM
    assert vmem_limit(w) > w


# ---------------------------------------------------------------------------
# expert_gmm (held-expert grouped product)
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("sizes", [(40, 0, 30, 58), (128, 0, 0, 0), (0, 0, 0, 256),
                                   (100, 100, 100, 84)],
                         ids=["ragged", "one-group", "none-held", "straddling"])
@pytest.mark.parametrize("depth", [1, 3], ids=["layer", "stack"])
def test_expert_gmm_matches_ref(sizes, depth):
    """Rows by held expert (the last group holds the rows of experts held
    elsewhere, which come out zero); groups may be empty or straddle a
    128-row tile; the weight stack is read at its last layer."""
    E, K, N = len(sizes) - 1, 64, 48
    lhs = jnp.asarray(RNG.normal(size=(sum(sizes), K)), jnp.float32)
    rhs = jnp.asarray(RNG.normal(size=(depth, E, K, N)), jnp.float32)
    gs = jnp.asarray(sizes, jnp.int32)
    layer = jnp.int32(depth - 1)
    got = np.asarray(expert_gmm(lhs, rhs, gs, layer))
    np.testing.assert_allclose(got, expert_gmm_ref(lhs, rhs, gs, layer), atol=1e-4, rtol=1e-5)
    np.testing.assert_array_equal(got[sum(sizes[:-1]):], 0.0)


# ---------------------------------------------------------------------------
# mla_decode (MLA latent attention, one query token a slot)
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("H,r,dr", [(4, 32, 8), (16, 512, 64)], ids=["tiny", "published"])
@pytest.mark.parametrize("S,tk", [(64, 16), (40, 16)], ids=["whole-tiles", "clamped-last-tile"])
@pytest.mark.parametrize("layer", ["first", "last"])
def test_mla_decode_matches_ref(H, r, dr, S, tk, layer):
    """One batch holds slots at positions 0, tk - 1, tk, S_max - 1 and two
    random ones, so a slot reads one tile, exactly one, two, every tile,
    and between; a cache that tk does not divide reads its last tile from
    S_max - tk.  The stack is read at its first or last layer."""
    G = 3
    pos = jnp.asarray([0, tk - 1, tk, S - 1, *RNG.integers(0, S, 2)], jnp.int32)
    B = pos.shape[0]
    q_lat = jnp.asarray(RNG.normal(size=(B, H, r)), jnp.float32)
    q_rope = jnp.asarray(RNG.normal(size=(B, H, dr)), jnp.float32)
    c_kv = jnp.asarray(RNG.normal(size=(G, B, S, r)), jnp.float32)
    k_rope = jnp.asarray(RNG.normal(size=(G, B, S, dr)), jnp.float32)
    g = jnp.int32(0 if layer == "first" else G - 1)
    scale = (dr + 16) ** -0.5
    got = mla_decode(q_lat, q_rope, c_kv, k_rope, g, pos, scale=scale, block_rows=tk)
    want = mla_decode_ref(q_lat[:, None], q_rope[:, None], c_kv, k_rope, g, pos[:, None],
                          scale=scale)[:, 0]
    np.testing.assert_allclose(np.asarray(got), np.asarray(want), atol=1e-5, rtol=1e-5)
