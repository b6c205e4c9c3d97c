"""Per-kernel shape/dtype sweeps: Pallas (interpret=True on CPU) vs the
ref.py pure-jnp oracles, assert_allclose."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.kernels.bmf_precision import ops as BMFK
from repro.kernels.decode_attention import ops as DECK
from repro.kernels.wkv6 import ops as WKVK
from repro.kernels.wkv6.ref import wkv_chunk_ref_batched

# ---------------------------------------------------------------------------
# bmf_precision
# ---------------------------------------------------------------------------


@pytest.mark.parametrize("N,M,K", [(5, 17, 8), (16, 64, 10), (33, 100, 100),
                                   (8, 256, 16), (3, 512, 32)])
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_bmf_precision_sweep(N, M, K, dtype):
    rng = np.random.default_rng(42)
    D = 50
    idx = jnp.asarray(rng.integers(0, D, (N, M)), jnp.int32)
    val = jnp.asarray(rng.normal(size=(N, M)), jnp.float32)
    mask = jnp.asarray(rng.random((N, M)) < 0.8, jnp.float32)
    other = jnp.asarray(rng.normal(size=(D, K)), dtype)
    tau = 2.5

    Lam, eta = BMFK.precision_accum(idx, val, mask, other, tau)
    Lam_r, eta_r = BMFK.precision_accum_reference(idx, val, mask, other, tau)
    # f32 tol covers tile-accumulation-order roundoff vs the single-einsum
    # oracle (the fused/chunked paths sum per M-tile)
    tol = 1e-4 if dtype == jnp.float32 else 2e-2
    np.testing.assert_allclose(np.asarray(Lam), np.asarray(Lam_r),
                               rtol=tol, atol=tol)
    np.testing.assert_allclose(np.asarray(eta), np.asarray(eta_r),
                               rtol=tol, atol=tol)


def _fused_case(rng, N, M, D, K, empty_rows=(), dtype=jnp.float32):
    idx = jnp.asarray(rng.integers(0, D, (N, M)), jnp.int32)
    val = jnp.asarray(rng.normal(size=(N, M)), jnp.float32)
    # contiguous-from-the-left CSR masks with ragged per-row occupancy
    nnz = rng.integers(0, M + 1, N)
    nnz[list(empty_rows)] = 0
    mask = jnp.asarray(np.arange(M)[None, :] < nnz[:, None], jnp.float32)
    other = jnp.asarray(rng.normal(size=(D, K)), dtype)
    return idx, val, mask, other


@pytest.mark.parametrize("N,M,K", [(5, 17, 8), (12, 40, 16), (9, 300, 128)])
def test_bmf_precision_fused_parity(N, M, K):
    """Fused-gather Pallas kernel (interpret mode) vs the dense oracle,
    with ragged occupancy and fully-empty rows (skipped M-tiles)."""
    rng = np.random.default_rng(7)
    idx, val, mask, other = _fused_case(rng, N, M, 37, K,
                                        empty_rows=(0, N - 1))
    Lam, eta = BMFK.precision_accum_fused(idx, val, mask, other, 1.3, tm=128)
    Lam_r, eta_r = BMFK.precision_accum_reference(idx, val, mask, other, 1.3)
    np.testing.assert_allclose(np.asarray(Lam), np.asarray(Lam_r),
                               rtol=1e-4, atol=1e-4)
    np.testing.assert_allclose(np.asarray(eta), np.asarray(eta_r),
                               rtol=1e-4, atol=1e-4)
    # empty rows must yield exactly-zero contributions
    assert float(jnp.abs(Lam[0]).max()) == 0.0
    assert float(jnp.abs(eta[-1]).max()) == 0.0


def _live_slot_case(rng, tm, D, K, dtype):
    """A plane for the row pump's live-slot walk: 4 row tiles over
    2·tm + 3 slots. Tile 0 is heavy (degrees 0, 1, tm−1, tm, tm+1 and
    2·tm+3), tile 1 light, tile 2 empty, and tile 3 has holes (row 24: a
    mask 0 before a live slot, extent 9 for degree 7; row 25: three holes
    across the first M-tile seam). Factor rows are multiples of 1/4 and
    ratings integers, so every sum is exact in any order and the kernel
    equals the one-einsum oracle bitwise. Factor row D−1 is NaN and only
    the live slot (6, 2·tm+1) of the heavy tile gathers it: the NaN it
    leaves in the gather scratch must reach no later tile. Returns idx,
    val, mask, other and the one row whose result is NaN."""
    deg = np.array([0, 1, tm - 1, tm, tm + 1, 2 * tm + 3, 2 * tm + 3, 5,
                    1, 0, 2, 3, 0, 1, 1, 2,
                    0, 0, 0, 0, 0, 0, 0, 0,
                    9, tm + 2, 3, 0, 0, 1, 0, 0])
    M = 2 * tm + 3
    mask = (np.arange(M)[None, :] < deg[:, None]).astype(np.float32)
    mask[24, [2, 5]] = 0.0
    mask[25, tm - 2:tm + 1] = 0.0
    idx = rng.integers(0, D - 1, (len(deg), M)).astype(np.int32)
    idx[6, 2 * tm + 1] = D - 1
    other = rng.integers(-4, 5, (D, K)) / 4.0
    other[D - 1] = np.nan
    val = rng.integers(1, 6, (len(deg), M)).astype(np.float32)
    return (jnp.asarray(idx), jnp.asarray(val), jnp.asarray(mask),
            jnp.asarray(other, dtype), 6)


@pytest.mark.parametrize("K", [8, 128])
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_bmf_precision_fused_live_slots_bitwise(K, dtype):
    """The pump copies only the slots below each row's extent; the slots it
    skips read as zero. Interpret-mode kernel vs the dense oracle,
    bitwise, on ragged degrees, an empty row tile, holes and a heavy tile
    followed by light ones."""
    rng = np.random.default_rng(29)
    idx, val, mask, other, nan_row = _live_slot_case(rng, 128, 41, K, dtype)
    Lam, eta = BMFK.precision_accum_fused(idx, val, mask, other, 2.0, tm=128)
    Lam_r, eta_r = BMFK.precision_accum_reference(idx, val, mask, other, 2.0)
    np.testing.assert_array_equal(np.asarray(Lam), np.asarray(Lam_r))
    np.testing.assert_array_equal(np.asarray(eta), np.asarray(eta_r))
    finite = np.arange(idx.shape[0]) != nan_row
    assert np.isfinite(np.asarray(Lam)[finite]).all()
    assert np.isnan(np.asarray(eta)[nan_row]).all()
    assert float(jnp.abs(Lam[16:24]).max()) == 0.0      # the empty tile


def test_bmf_precision_fused_n_striping():
    """A tiny SMEM budget forces the wrapper to stripe the N axis into
    several pallas_calls; parity with the oracle must hold across the
    stripe seams."""
    rng = np.random.default_rng(17)
    idx, val, mask, other = _fused_case(rng, 40, 50, 30, 8, empty_rows=(11,))
    # one TN-row stripe per call: 8 rows × Mp=128 slots × 4 B = 4 KB budget
    Lam, eta = BMFK.precision_accum_fused(idx, val, mask, other, 2.0,
                                          tm=128, smem_idx_budget=4096)
    Lam_r, eta_r = BMFK.precision_accum_reference(idx, val, mask, other, 2.0)
    np.testing.assert_allclose(np.asarray(Lam), np.asarray(Lam_r),
                               rtol=1e-4, atol=1e-4)
    np.testing.assert_allclose(np.asarray(eta), np.asarray(eta_r),
                               rtol=1e-4, atol=1e-4)


def test_bmf_precision_fused_bf16_and_truncated_rows():
    """bf16 factors + CSR built with truncating max_nnz bucketing."""
    from repro.data.sparse import COO, coo_to_padded_csr
    rng = np.random.default_rng(11)
    n_rows, n_cols, nnz = 19, 23, 400
    coo = COO(row=rng.integers(0, n_rows, nnz).astype(np.int32),
              col=rng.integers(0, n_cols, nnz).astype(np.int32),
              val=rng.normal(size=nnz).astype(np.float32),
              n_rows=n_rows, n_cols=n_cols)
    csr = coo_to_padded_csr(coo, max_nnz=16)      # truncates heavy rows
    other = jnp.asarray(rng.normal(size=(n_cols, 8)), jnp.bfloat16)
    Lam, eta = BMFK.precision_accum_fused(csr.idx, csr.val, csr.mask,
                                          other, 2.0, tm=128)
    Lam_r, eta_r = BMFK.precision_accum_reference(csr.idx, csr.val, csr.mask,
                                                  other, 2.0)
    np.testing.assert_allclose(np.asarray(Lam), np.asarray(Lam_r),
                               rtol=2e-2, atol=2e-2)
    np.testing.assert_allclose(np.asarray(eta), np.asarray(eta_r),
                               rtol=2e-2, atol=2e-2)


def test_bmf_precision_no_gather_materialization():
    """Regression: no path of ``precision_accum`` may have an (N, M, K)-sized
    live buffer — the fused kernel gathers inside, the XLA fallback chunks.
    The dense reference DOES materialize it (sanity check that the probe
    bites)."""
    from repro.roofline.jaxpr_cost import iter_avals
    rng = np.random.default_rng(13)
    N, M, D, K = 32, 8192, 64, 16   # N·M·K well above CHUNK_BUDGET_ELEMS
    idx, val, mask, other = _fused_case(rng, N, M, D, K)
    budget = N * M * K          # elements of the banned gathered tensor

    def peak(fn):
        jaxpr = jax.make_jaxpr(fn)(idx, val, mask, other)
        return max(int(np.prod(a.shape)) for a in iter_avals(jaxpr)
                   if a.shape)

    assert peak(lambda *a: BMFK.precision_accum(*a, tau=2.0)) < budget
    assert peak(lambda *a: BMFK.precision_accum_chunked(*a, 2.0)) < budget
    assert peak(lambda *a: BMFK.precision_accum_fused(*a, 2.0)) < budget
    assert peak(lambda *a: BMFK.precision_accum_reference(*a, 2.0)) >= budget


def test_row_extent_and_pump_copies():
    """The pump's extent plane and its copy counter against a hand count:
    a left-filled row copies its degree, a row with holes its last live
    slot + 1, an empty row nothing."""
    from repro.data.sparse import pump_copies, row_extent
    mask = np.zeros((16, 12), np.float32)
    mask[0, :5] = 1.0                  # degree 5: slots 0-4
    mask[1, [0, 3, 9]] = 1.0           # degree 3, holes: slots 0-9
    mask[2, :1] = 1.0                  # degree 1
    mask[8, :12] = 1.0                 # a full row; rows 3-7, 9-15 empty
    ext = np.asarray(row_extent(jnp.asarray(mask)))
    np.testing.assert_array_equal(ext[:3], [5, 10, 1])
    assert ext[8] == 12 and ext[3:8].sum() == 0 and ext[9:].sum() == 0
    assert pump_copies(mask) == 5 + 10 + 1 + 12 == 28
    assert pump_copies(mask) - int(mask.sum()) == 10 - 3     # the holes
    assert pump_copies(np.zeros((8, 4), np.float32)) == 0


# ---------------------------------------------------------------------------
# decode_attention
# ---------------------------------------------------------------------------


@pytest.mark.parametrize("B,H,Hkv,hd,S", [
    (2, 8, 2, 64, 512), (1, 4, 4, 128, 1024), (2, 16, 8, 64, 700),
    (1, 32, 8, 128, 512),
])
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_decode_attention_sweep(B, H, Hkv, hd, S, dtype):
    rng = np.random.default_rng(0)
    q = jnp.asarray(rng.normal(size=(B, H, hd)), dtype)
    k = jnp.asarray(rng.normal(size=(B, S, Hkv, hd)), dtype)
    v = jnp.asarray(rng.normal(size=(B, S, Hkv, hd)), dtype)
    q_pos = S - 100
    kv_pos = jnp.where(jnp.arange(S) <= q_pos, jnp.arange(S), -1)

    out = DECK.decode_attention(q, k, v, kv_pos, q_pos)
    ref = DECK.decode_attention_reference(q, k, v, kv_pos, q_pos)
    tol = 1e-5 if dtype == jnp.float32 else 3e-2
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                               rtol=tol, atol=tol)


def test_decode_attention_sliding_window():
    rng = np.random.default_rng(1)
    B, H, Hkv, hd, S = 1, 4, 2, 64, 512
    q = jnp.asarray(rng.normal(size=(B, H, hd)), jnp.float32)
    k = jnp.asarray(rng.normal(size=(B, S, Hkv, hd)), jnp.float32)
    v = jnp.asarray(rng.normal(size=(B, S, Hkv, hd)), jnp.float32)
    q_pos = 400
    kv_pos = jnp.where(jnp.arange(S) <= q_pos, jnp.arange(S), -1)
    for window in (64, 128):
        out = DECK.decode_attention(q, k, v, kv_pos, q_pos, window=window)
        ref = DECK.decode_attention_reference(q, k, v, kv_pos, q_pos,
                                              window=window)
        np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                                   rtol=1e-5, atol=1e-5)


def test_decode_attention_ring_cache_positions():
    """Slots out of temporal order (ring buffer) must still mask correctly."""
    rng = np.random.default_rng(2)
    B, H, Hkv, hd, S = 1, 2, 1, 64, 512
    q = jnp.asarray(rng.normal(size=(B, H, hd)), jnp.float32)
    k = jnp.asarray(rng.normal(size=(B, S, Hkv, hd)), jnp.float32)
    v = jnp.asarray(rng.normal(size=(B, S, Hkv, hd)), jnp.float32)
    # ring layout: slot i holds position (1000 - S + i) for first half,
    # second half empty
    pos = np.full(S, -1, np.int32)
    pos[:256] = 700 + np.arange(256)
    kv_pos = jnp.asarray(np.roll(pos, 40))
    q_pos = 955
    out = DECK.decode_attention(q, k, v, kv_pos, q_pos, window=128)
    ref = DECK.decode_attention_reference(q, k, v, kv_pos, q_pos, window=128)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                               rtol=1e-5, atol=1e-5)


# ---------------------------------------------------------------------------
# wkv6
# ---------------------------------------------------------------------------


@pytest.mark.parametrize("B,S,H,N", [(1, 128, 2, 64), (2, 256, 1, 64),
                                     (1, 384, 4, 32)])
def test_wkv6_sweep(B, S, H, N):
    rng = np.random.default_rng(3)
    r = jnp.asarray(rng.normal(size=(B, S, H, N)), jnp.float32)
    k = jnp.asarray(rng.normal(size=(B, S, H, N)), jnp.float32) * 0.5
    v = jnp.asarray(rng.normal(size=(B, S, H, N)), jnp.float32)
    logw = -jnp.exp(jnp.asarray(rng.normal(size=(B, S, H, N)), jnp.float32) - 2)
    u = jnp.asarray(rng.normal(size=(H, N)), jnp.float32) * 0.1
    s0 = jnp.asarray(rng.normal(size=(B, H, N, N)), jnp.float32) * 0.1

    y, st = WKVK.wkv6(r, k, v, logw, u, s0)
    y_ref, st_ref = WKVK.wkv6_reference(r, k, v, logw, u, s0)
    np.testing.assert_allclose(np.asarray(y), np.asarray(y_ref),
                               rtol=2e-4, atol=2e-4)
    np.testing.assert_allclose(np.asarray(st), np.asarray(st_ref),
                               rtol=2e-4, atol=2e-4)


# ---------------------------------------------------------------------------
# ssd_chunk (mamba2)
# ---------------------------------------------------------------------------


@pytest.mark.parametrize("B,S,H,P,N", [(1, 128, 2, 64, 64), (2, 256, 3, 32, 16),
                                       (1, 384, 1, 64, 64)])
def test_ssd_chunk_sweep(B, S, H, P, N):
    from repro.kernels.ssd_chunk import ops as SSDK
    rng = np.random.default_rng(5)
    xdt = jnp.asarray(rng.normal(size=(B, S, H, P)), jnp.float32) * 0.5
    a = -jnp.exp(jnp.asarray(rng.normal(size=(B, S, H)), jnp.float32) - 1)
    B_ = jnp.asarray(rng.normal(size=(B, S, N)), jnp.float32) * 0.5
    C_ = jnp.asarray(rng.normal(size=(B, S, N)), jnp.float32) * 0.5
    s0 = jnp.asarray(rng.normal(size=(B, H, P, N)), jnp.float32) * 0.1
    y, st = SSDK.ssd_scan(xdt, a, B_, C_, s0)
    y_ref, st_ref = SSDK.ssd_scan_reference(xdt, a, B_, C_, s0)
    np.testing.assert_allclose(np.asarray(y), np.asarray(y_ref),
                               rtol=2e-4, atol=2e-4)
    np.testing.assert_allclose(np.asarray(st), np.asarray(st_ref),
                               rtol=2e-4, atol=2e-4)
