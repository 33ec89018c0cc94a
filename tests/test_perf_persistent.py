"""PR 3 perf-path tests: persistent device-side decode vs the legacy
per-token loop (token-for-token parity), ragged-shape pad/mask in the
generated kernel, the C-slow-batched fused kernel vs the
``cslow_vectorized`` oracle, and the int8 gate MACC vs ``int8_matmul``."""

import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.codegen import (
    CELL_GRAPHS,
    GraphBuilder,
    Schedule,
    Stage,
    bind_cell_params,
    compile_spec,
    pallas_backend,
    xla_backend,
)
from repro.configs import get_smoke_config
from repro.core.synthesis import NetworkSpec
from repro.kernels.int8_matmul.ops import quantized_matmul
from repro.models import lm
from repro.recurrent import cells as rnn_cells
from repro.runtime import DecodeServer, Request


# ---------------------------------------------------------------------------
# persistent decode ≡ legacy per-token loop
# ---------------------------------------------------------------------------

def _requests(vocab: int, n: int = 5, max_new: int = 6, seed: int = 0):
    rng = np.random.default_rng(seed)
    return [
        Request(uid=i,
                prompt=list(rng.integers(1, vocab, size=int(rng.integers(2, 6)))),
                max_new_tokens=max_new)
        for i in range(n)
    ]


def _drain(cfg, params, *, persistent, block_k=8, eos_id=None, reqs=None,
           slots=3, max_seq=48):
    srv = DecodeServer(cfg, params, num_slots=slots, max_seq=max_seq,
                       eos_id=eos_id, block_k=block_k, persistent=persistent)
    for r in reqs or _requests(cfg.vocab):
        srv.submit(r)
    done = srv.run_until_drained()
    return {r.uid: list(r.out_tokens) for r in done}, srv


@pytest.fixture(scope="module")
def smollm():
    cfg = get_smoke_config("smollm-135m")
    return cfg, lm.init_params(cfg, jax.random.PRNGKey(0))


@pytest.mark.parametrize("block_k", [1, 4, 8])
def test_persistent_greedy_parity(smollm, block_k):
    """Same seeds → identical greedy tokens, any K (incl. K=1)."""
    cfg, params = smollm
    legacy, _ = _drain(cfg, params, persistent=False)
    persist, _ = _drain(cfg, params, persistent=True, block_k=block_k)
    assert legacy == persist


def test_persistent_eos_and_oom_edges(smollm):
    """EOS mid-block and max-seq exhaustion retire identically."""
    cfg, params = smollm
    legacy, _ = _drain(cfg, params, persistent=False)
    # pick a token the model actually emits mid-stream as the EOS id —
    # deterministic EOS coverage on both paths
    eos = legacy[0][2]
    reqs = lambda: _requests(cfg.vocab, max_new=12)
    l2, _ = _drain(cfg, params, persistent=False, eos_id=eos, reqs=reqs(),
                   max_seq=24)   # small max_seq: some slots hit the oom stop
    p2, _ = _drain(cfg, params, persistent=True, eos_id=eos, reqs=reqs(),
                   max_seq=24)
    assert l2 == p2
    assert any(toks[-1] == eos for toks in l2.values())  # EOS path exercised


def test_persistent_midstream_admit(smollm):
    """Requests admitted while other slots are mid-generation (more requests
    than slots, staggered lengths) still decode token-identically."""
    cfg, params = smollm
    def reqs():
        out = _requests(cfg.vocab, n=7, max_new=5, seed=3)
        for i, r in enumerate(out):   # staggered: slots free up at odd ticks
            r.max_new_tokens = 3 + (i % 4)
        return out
    legacy, _ = _drain(cfg, params, persistent=False, reqs=reqs(), slots=2)
    persist, _ = _drain(cfg, params, persistent=True, block_k=4, reqs=reqs(),
                        slots=2)
    assert legacy == persist


def test_persistent_sync_budget(smollm):
    """The acceptance metric: ≥K tokens per host sync for K-step blocks."""
    cfg, params = smollm
    K = 8
    reqs = _requests(cfg.vocab, n=4, max_new=16, seed=1)
    _, srv = _drain(cfg, params, persistent=True, block_k=K, reqs=reqs,
                    slots=2, max_seq=64)
    stats = srv.stats()
    assert stats["decoded_tokens"] == sum(r.max_new_tokens - 1 for r in reqs)
    assert stats["syncs_per_token"] <= 1.0 / K
    # legacy pays ≥1 sync per tick — strictly more round-trips
    _, srv_l = _drain(cfg, params, persistent=False,
                      reqs=_requests(cfg.vocab, n=4, max_new=16, seed=1),
                      slots=2, max_seq=64)
    assert srv_l.stats()["decode_syncs"] >= 5 * stats["decode_syncs"]


def test_persistent_temperature_terminates(smollm):
    """Sampled (temperature>0) slots decode on device and retire."""
    cfg, params = smollm
    reqs = _requests(cfg.vocab, n=3, max_new=5, seed=2)
    for r in reqs:
        r.temperature = 0.8
    done, srv = _drain(cfg, params, persistent=True, block_k=4, reqs=reqs)
    assert len(done) == 3
    assert all(len(t) == 5 for t in done.values())


def test_persistent_recurrent_arch(smollm):
    """Recurrent (h, c) carries ride the K-step scan — the splice_cache
    layout is the scan carry layout."""
    cfg = get_smoke_config("paper-lstm")
    params = lm.init_params(cfg, jax.random.PRNGKey(0))
    legacy, _ = _drain(cfg, params, persistent=False,
                       reqs=_requests(cfg.vocab, n=4, max_new=4), slots=2)
    persist, _ = _drain(cfg, params, persistent=True, block_k=4,
                        reqs=_requests(cfg.vocab, n=4, max_new=4), slots=2)
    assert legacy == persist


# ---------------------------------------------------------------------------
# the block's host boundary: one packed array each way
# ---------------------------------------------------------------------------

_S = 48
# (cur, pos, live, remaining, temps) over B=4 slots
_BLOCK_INPUTS = {
    "edge_temps": ([3, 7, 0, 11], [5, _S - 1, 0, 9], [1, 1, 0, 1],
                   [4, 2, 0, -3], [0.0, 1e-6, 1.7, 0.0]),
    "all_dead": ([0, 0, 0, 0], [0, 0, 0, 0], [0, 0, 0, 0],
                 [0, -1, 0, 0], [0.0, 0.0, 0.0, 0.0]),
    "all_live": ([1, 2, 3, 4], [_S - 1, 1, _S - 2, 7], [1, 1, 1, 1],
                 [1, 8, 3, 100], [1.7, 1e-6, 0.0, 0.8]),
}


def _block_inputs(case):
    cur, pos, live, remaining, temps = _BLOCK_INPUTS[case]
    return (np.array(cur, np.int32), np.array(pos, np.int32),
            np.array(live, bool), np.array(remaining, np.int32),
            np.array(temps, np.float32))


@pytest.mark.parametrize("case", sorted(_BLOCK_INPUTS))
def test_block_inputs_round_trip(case):
    """Host pack → device unpack gives every input back, temps bit-exact."""
    from repro.runtime.server import pack_block_inputs, unpack_block_inputs

    want = _block_inputs(case)
    packed = pack_block_inputs(*want)
    assert packed.dtype == np.int32 and packed.shape == (5, 4)
    got = jax.jit(unpack_block_inputs)(jnp.asarray(packed))
    for w, g in zip(want, got):
        g = np.asarray(g)
        assert g.dtype == w.dtype
        np.testing.assert_array_equal(g.view(np.uint8), w.view(np.uint8))


def test_block_outputs_round_trip():
    """Device pack → host unpack, with a slot that retires mid-block: every
    output comes back, and the arrays the host writes are writable."""
    from repro.runtime.server import pack_block_outputs, unpack_block_outputs

    K, B = 4, 3
    toks = np.arange(K * B, dtype=np.int32).reshape(K, B) + 30_000
    emitted = np.ones((K, B), bool)
    emitted[2:, 1] = False               # slot 1 retires at tick 1
    emitted[:, 2] = False                # slot 2 is dead all block
    done_now = np.zeros((K, B), bool)
    done_now[1, 1] = True
    finite = np.ones((K, B), bool)
    finite[3, 0] = False
    cur = np.array([5, 7, 0], np.int32)
    pos = np.array([_S - 1, 9, 0], np.int32)
    live = np.array([True, False, False])
    want = (toks, emitted, done_now, finite, cur, pos, live)
    packed = np.asarray(jax.jit(pack_block_outputs)(*map(jnp.asarray, want)))
    assert packed.dtype == np.int32 and packed.shape == (4 * K + 3, B)
    got = unpack_block_outputs(packed, K)
    for w, g in zip(want, got):
        assert g.dtype == w.dtype
        np.testing.assert_array_equal(g, w)
    for g in got[1:]:
        assert g.flags.writeable


@pytest.mark.parametrize("case", sorted(_BLOCK_INPUTS))
def test_packed_block_matches_scan(smollm, case):
    """The dispatched block is the K-step scan, bit for bit, behind the
    packed boundary: tokens, flags, carries, caches and key."""
    from repro.runtime.server import (pack_block_inputs,
                                      unpack_block_outputs)

    cfg, params = smollm
    K = 4
    srv = DecodeServer(cfg, params, num_slots=4, max_seq=_S, block_k=K,
                       persistent=True)
    ins = _block_inputs(case)
    carry, outs = srv._make_block_fn(K)(params, srv.caches,
                                        *map(jnp.asarray, ins), srv.key)
    caches, key, packed = srv._make_packed_block_fn(K)(
        params, srv.caches, jnp.asarray(pack_block_inputs(*ins)), srv.key)
    got = unpack_block_outputs(np.asarray(packed), K)
    want = (*outs, carry[1], carry[2], carry[3])
    for w, g in zip(want, got):
        np.testing.assert_array_equal(g, np.asarray(w))
    np.testing.assert_array_equal(np.asarray(key), np.asarray(carry[5]))
    for a, b in zip(jax.tree.leaves(caches), jax.tree.leaves(carry[0])):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))


def test_one_transfer_each_way_per_block(smollm):
    """A persistent server crosses the block boundary once each way."""
    cfg, params = smollm
    reqs = _requests(cfg.vocab, n=5, max_new=11, seed=4)
    _, srv = _drain(cfg, params, persistent=True, block_k=4, reqs=reqs,
                    slots=3)
    s = srv.stats()
    assert s["decode_syncs"] > 2
    assert s["d2h_per_block"] == 1 and s["h2d_per_block"] == 1
    m = srv.obs.metrics
    assert m.value("block_transfers", dir="d2h") == s["decode_syncs"]
    assert m.value("block_transfers", dir="h2d") == s["decode_syncs"]


# ---------------------------------------------------------------------------
# ragged shapes: pad + mask instead of degrade/crash (satellite 1)
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("cell,B,T", [("gru", 5, 13), ("lstm", 7, 11),
                                      ("ssm", 3, 17)])
def test_ragged_prime_shapes_match_xla(cell, B, T):
    D, H = 3, 8
    graph = CELL_GRAPHS[cell](D, H)
    stage = Stage(name=cell, graph=graph, schedule=Schedule(steps=T), params={})
    key = jax.random.PRNGKey(0)
    if cell == "ssm":
        from repro.codegen import ssm_params
        cell_p = ssm_params(key, D, H)
    else:
        ctor = rnn_cells.lstm_params if cell == "lstm" else rnn_cells.gru_params
        cell_p = ctor(key, D, H)
    consts = bind_cell_params(cell, cell_p)
    us = jax.random.normal(jax.random.PRNGKey(1), (B, T, D))
    x0 = {n: jnp.zeros((B, w)) for n, w in graph.states.items()}
    # chunk=4, block_b=2: neither divides the prime sizes — forces pad+mask
    fin_p, ys_p = pallas_backend.compile_stage(stage, chunk=4, block_b=2)(
        consts, x0, us)
    fin_x, ys_x = xla_backend.compile_stage(stage)(consts, x0, us)
    assert ys_p.shape == (B, T, graph.node(graph.output).width)
    np.testing.assert_allclose(np.asarray(ys_p), np.asarray(ys_x), atol=1e-5)
    for n in graph.states:
        np.testing.assert_allclose(np.asarray(fin_p[n]), np.asarray(fin_x[n]),
                                   atol=1e-5)


def test_ragged_mlp_per_step_roms():
    """Prime layer count: per-step ROM pages are padded and masked (the
    double-buffered DMA path streams the padded pages)."""
    spec = NetworkSpec(3, 7, 4, 2)
    p1, f1 = compile_spec(spec, backend="xla")
    p2, f2 = compile_spec(spec, backend="pallas")
    u = jax.random.normal(jax.random.PRNGKey(2), (5, 3))
    np.testing.assert_allclose(np.asarray(f1(p1, u)), np.asarray(f2(p2, u)),
                               atol=1e-5)


def test_double_buffer_off_is_equivalent():
    """The BlockSpec fallback (double_buffer=False) matches the DMA path."""
    spec = NetworkSpec(3, 5, 4, 2)
    prog_fwd = {}
    for db in (True, False):
        from repro.codegen import build_program
        prog = build_program(spec)
        fwd = pallas_backend.compile_program(prog, double_buffer=db)
        prog_fwd[db] = np.asarray(fwd(prog.params,
                                      jax.random.normal(jax.random.PRNGKey(3),
                                                        (4, 3))))
    np.testing.assert_allclose(prog_fwd[True], prog_fwd[False], atol=1e-6)


# ---------------------------------------------------------------------------
# C-slow as batching: fused kernel ≡ cslow_vectorized oracle
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("cell", ["gru", "lstm"])
def test_cslow_batched_kernel_matches_vectorized_oracle(cell):
    """`synthesize(backend="pallas")` with c_slow=C runs ONE fused kernel
    over C·B folded streams; the XLA path runs ``cslow_vectorized``'s
    vmap-of-scans.  ≤1e-5 in fp32 interpret mode (acceptance criterion) —
    ragged seq_len so the fold also crosses the pad/mask path."""
    spec = NetworkSpec(3, 2, 8, 2, cell=cell, seq_len=13, c_slow=3)
    px, fx = compile_spec(spec, backend="xla")       # cslow_vectorized oracle
    pp, fp = compile_spec(spec, backend="pallas")    # batch-folded fused kernel
    uc = jax.random.normal(jax.random.PRNGKey(4), (3, 5, 13, 3))
    np.testing.assert_allclose(np.asarray(fp(pp, uc)), np.asarray(fx(px, uc)),
                               atol=1e-5)


def test_fold_streams_roundtrip():
    from repro.core.cslow import fold_streams, unfold_streams

    u = jax.random.normal(jax.random.PRNGKey(0), (3, 4, 7, 2))
    folded = fold_streams(u)
    assert folded.shape == (12, 7, 2)
    np.testing.assert_array_equal(np.asarray(unfold_streams(folded, 3)),
                                  np.asarray(u))


# ---------------------------------------------------------------------------
# int8 gate MACC (paper's fixed-point datapath)
# ---------------------------------------------------------------------------

def test_int8_macc_weight_only_semantics():
    """A one-macc graph on the quantized path computes ``x @ dequant(W)``
    exactly (weight-only int8: per-output-channel scale fused after the
    dot), and pre-packed int8 consts (``prequantize_consts``) reproduce the
    raw-float-const path bit for bit — the contract that lets synthesis
    pack ROM pages once and stream them through the double-buffer DMA."""
    from repro.kernels.int8_matmul.ops import quantize_per_channel

    D, N, B = 6, 8, 4
    g = GraphBuilder()
    u = g.input("u", D)
    g.state("h", N)
    W = g.const("W", (D, N))
    z = g.macc("z", u, W)
    g.update("h", z)
    graph = g.build(output=z)
    stage = Stage(name="mm", graph=graph, schedule=Schedule(steps=1), params={})
    run = pallas_backend.compile_stage(stage, quant_bits=8)
    Wv = jax.random.normal(jax.random.PRNGKey(0), (D, N))
    x = jax.random.normal(jax.random.PRNGKey(1), (B, D))
    _, ys = run({"W": Wv}, {"h": jnp.zeros((B, N))}, x[:, None, :])
    w_q, s = quantize_per_channel(Wv, axis=-2)
    ref = (x @ w_q.astype(jnp.float32)) * s        # weight-only reference
    np.testing.assert_allclose(np.asarray(ys[:, 0]), np.asarray(ref),
                               atol=1e-6)
    # activations are NOT quantized on this path (the old dynamic-activation
    # datapath is gone): full-precision x flows into the dot
    assert not np.allclose(np.asarray(ref), np.asarray(quantized_matmul(x, Wv)),
                           atol=1e-6)
    packed = pallas_backend.prequantize_consts(graph, {"W": Wv}, 8)
    assert packed["W"].dtype == jnp.int8 and "W.scale" in packed
    _, ys2 = run(packed, {"h": jnp.zeros((B, N))}, x[:, None, :])
    np.testing.assert_array_equal(np.asarray(ys), np.asarray(ys2))


@pytest.mark.parametrize("cell", ["lstm", "gru", "ssm"])
def test_int8_gate_path_within_quant_tolerance(cell):
    """Full cells on the int8 MACC datapath track fp32 within the expected
    8-bit error envelope — and actually differ (the path is live)."""
    spec = NetworkSpec(3, 1, 8, 2, cell=cell, seq_len=12)
    from repro.codegen import build_program
    prog = build_program(spec)
    f_fp = pallas_backend.compile_program(prog)
    f_q8 = pallas_backend.compile_program(prog, quant_bits=8)
    u = jax.random.normal(jax.random.PRNGKey(5), (4, 12, 3))
    a, b = np.asarray(f_fp(prog.params, u)), np.asarray(f_q8(prog.params, u))
    err = np.abs(a - b).max()
    scale = max(np.abs(a).max(), 1e-3)
    assert 0 < err < 0.15 * scale


def test_int8_composes_with_lut_gates():
    """quant_bits<=8 through synthesize: int8 MACC + ROM-LUT activations in
    the same generated kernel (the paper's full fixed-point datapath)."""
    from repro.core.synthesis import synthesize

    spec = NetworkSpec(3, 2, 8, 2, cell="lstm", seq_len=8, quant_bits=8)
    rep = synthesize(spec, batch=2, backend="pallas")
    assert rep.quant["mode"] == "lut" and rep.quant["int8_macc"]
    ssm = NetworkSpec(3, 2, 8, 2, cell="ssm", seq_len=8, quant_bits=8)
    rep2 = synthesize(ssm, batch=2, backend="pallas")
    assert rep2.quant["mode"] == "int8"
    # >8 bits on an af-free cell still has nothing to quantize on pallas
    with pytest.raises(ValueError, match="not supported"):
        synthesize(dataclasses.replace(ssm, quant_bits=16), batch=2,
                   backend="pallas")


def test_block_fast_path_int8_gates():
    """cfg.quant_gate_bits routes the recurrent block's generated-kernel
    prefill through the int8 gate contraction."""
    from repro.configs.paper_lstm import smoke_config

    base = smoke_config()
    cfg = dataclasses.replace(base, use_codegen=True, quant_gate_bits=8)
    params = lm.init_params(base, jax.random.PRNGKey(0))
    toks = jax.random.randint(jax.random.PRNGKey(1), (2, 16), 0, base.vocab)
    ref, _ = lm.prefill(params, base, toks)
    got, _ = lm.prefill(params, cfg, toks)
    err = np.abs(np.asarray(got) - np.asarray(ref)).max()
    assert 0 < err < 0.15 * np.abs(np.asarray(ref)).max()
