"""Per-kernel interpret-mode validation against the pure-jnp oracles.

Every Pallas kernel is swept over shapes/dtypes (hypothesis) and checked
against ``repro.kernels.ref`` — the contract the system relies on when it
dispatches kernels on TPU.  The radar kernels must match bitwise; the
sweeps pass a small ``vmem_budget`` so that the shape-derived tiles split
every axis into several (ragged) grid steps.  The oracle runs under
``jax.jit``, as one XLA program like the interpreted kernel body: eager
op-by-op dispatch rounds the fused transcendental and reduction steps
differently in the last bit.
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from _hypothesis_compat import given, settings, strategies as st

from repro.kernels import ref
from repro.kernels.flash_attention import flash_attention_pallas
from repro.kernels.grid_map import grid_map_pallas
from repro.kernels.grid_update import grid_update_pallas
from repro.kernels.mamba2_scan import mamba2_scan_pallas
from repro.kernels.qvp_reduce import qvp_reduce_pallas
from repro.kernels.zr_accum import zr_accum_pallas


def _radar_field(rng, t, a, r, nan_frac=0.15):
    f = rng.normal(20.0, 12.0, size=(t, a, r)).astype(np.float32)
    f[rng.random((t, a, r)) < nan_frac] = np.nan
    return f


# ---------------------------------------------------------------------------
# qvp_reduce
# ---------------------------------------------------------------------------

@given(
    t=st.integers(1, 9),
    a=st.integers(4, 48),
    r=st.integers(3, 300),
    seed=st.integers(0, 999),
)
@settings(max_examples=20, deadline=None)
def test_qvp_reduce_matches_ref(t, a, r, seed):
    rng = np.random.default_rng(seed)
    field = _radar_field(rng, t, a, r)
    quality = rng.uniform(0.5, 1.0, size=(t, a, r)).astype(np.float32)
    got = qvp_reduce_pallas(field, quality, vmem_budget=64 * 1024,
                            interpret=True)
    want = jax.jit(ref.qvp_reduce)(field, quality)
    np.testing.assert_array_equal(np.asarray(got), np.asarray(want))


def test_qvp_reduce_no_quality_path():
    rng = np.random.default_rng(0)
    field = _radar_field(rng, 4, 360, 250)
    got = qvp_reduce_pallas(field, field, quality_min=float("-inf"),
                            interpret=True)
    want = ref.qvp_reduce(field, None)
    np.testing.assert_allclose(got, want, rtol=1e-5, atol=1e-5)


def test_qvp_reduce_all_invalid_row_is_nan():
    field = np.full((2, 8, 16), np.nan, dtype=np.float32)
    out = qvp_reduce_pallas(field, np.ones_like(field), interpret=True)
    assert np.isnan(np.asarray(out)).all()


# ---------------------------------------------------------------------------
# grid_map
# ---------------------------------------------------------------------------

@given(
    t=st.integers(1, 9),
    g=st.integers(8, 4000),
    c=st.integers(1, 3000),
    k=st.sampled_from([1, 2, 4, 8]),
    seed=st.integers(0, 999),
)
@settings(max_examples=20, deadline=None)
def test_grid_map_matches_ref_bitwise(t, g, c, k, seed):
    """Interpret mode must equal the oracle *bitwise* (same op order) —
    the equality bench_grid.py gates in CI."""
    rng = np.random.default_rng(seed)
    field = rng.normal(20.0, 12.0, size=(t, g)).astype(np.float32)
    field[rng.random((t, g)) < 0.2] = np.nan
    idx = rng.integers(0, g, size=(c, k)).astype(np.int32)
    w = rng.uniform(0.0, 2.0, size=(c, k)).astype(np.float32)
    w[rng.random((c, k)) < 0.3] = 0.0     # dropped neighbours
    got = np.asarray(grid_map_pallas(field, idx, w, vmem_budget=128 * 1024,
                                     interpret=True))
    want = np.asarray(ref.grid_map(field, idx, w))
    np.testing.assert_array_equal(got, want)


def test_grid_map_nearest_is_plain_gather():
    """k=1 unit weights: each cell is exactly its gate's value."""
    rng = np.random.default_rng(1)
    field = rng.normal(size=(3, 50)).astype(np.float32)
    idx = rng.integers(0, 50, size=(20, 1)).astype(np.int32)
    w = np.ones((20, 1), np.float32)
    out = np.asarray(grid_map_pallas(field, idx, w, interpret=True))
    np.testing.assert_array_equal(out, field[:, idx[:, 0]])


def test_grid_map_zero_weight_cell_is_nan():
    """Cells out of radar reach (all weights 0) come back NaN."""
    field = np.ones((2, 16), np.float32)
    idx = np.zeros((5, 4), np.int32)
    w = np.zeros((5, 4), np.float32)
    w[2] = 1.0  # one in-reach cell
    out = np.asarray(grid_map_pallas(field, idx, w, interpret=True))
    assert np.isnan(out[:, [0, 1, 3, 4]]).all()
    np.testing.assert_array_equal(out[:, 2], 1.0)


def test_grid_map_empty_axes_match_ref():
    """T=0 (empty planner window) and C=0 must not crash the tiler and
    must agree with the oracle's empty results."""
    idx = np.zeros((5, 2), np.int32)
    w = np.ones((5, 2), np.float32)
    out = np.asarray(grid_map_pallas(np.empty((0, 16), np.float32), idx, w,
                                     interpret=True))
    want = np.asarray(ref.grid_map(np.empty((0, 16), np.float32), idx, w))
    assert out.shape == want.shape == (0, 5)
    out = np.asarray(grid_map_pallas(
        np.ones((3, 16), np.float32), np.zeros((0, 2), np.int32),
        np.zeros((0, 2), np.float32), interpret=True,
    ))
    assert out.shape == (3, 0)


def test_grid_map_skips_nan_gates():
    """A NaN neighbour drops out of the weighted mean instead of
    poisoning the cell."""
    field = np.array([[1.0, np.nan, 3.0]], np.float32)
    idx = np.array([[0, 1], [1, 2]], np.int32)
    w = np.ones((2, 2), np.float32)
    out = np.asarray(grid_map_pallas(field, idx, w, interpret=True))
    np.testing.assert_allclose(out, [[1.0, 3.0]])


# ---------------------------------------------------------------------------
# grid_update
# ---------------------------------------------------------------------------

@given(
    t=st.integers(1, 9),
    c=st.integers(1, 3000),
    seed=st.integers(0, 999),
    op=st.sampled_from(["set", "add", "max"]),
    touched_frac=st.sampled_from([0.0, 0.1, 0.5, 1.0]),
)
@settings(max_examples=20, deadline=None)
def test_grid_update_matches_ref_bitwise(t, c, seed, op, touched_frac):
    """Interpret mode must equal the oracle *bitwise* (same op order) —
    incremental products rely on it for the from-scratch equality the
    streaming bench gates in CI."""
    rng = np.random.default_rng(seed)
    state = rng.normal(20.0, 12.0, size=(t, c)).astype(np.float32)
    state[rng.random((t, c)) < 0.2] = np.nan
    touched = rng.random(c) < touched_frac
    m = int(touched.sum())
    pos = np.full(c, -1, np.int32)
    pos[touched] = rng.permutation(m).astype(np.int32)
    upd = rng.normal(20.0, 12.0, size=(t, m)).astype(np.float32)
    upd[rng.random((t, m)) < 0.2] = np.nan
    got = np.asarray(grid_update_pallas(state, upd, pos, op=op,
                                        vmem_budget=128 * 1024,
                                        interpret=True))
    want = np.asarray(ref.grid_update(state, upd, pos, op=op))
    np.testing.assert_array_equal(got, want)


def test_grid_update_untouched_cells_pass_through_bitwise():
    """pos == -1 cells must keep their state bit-for-bit (NaN included)."""
    state = np.array([[1.0, np.nan, 3.0, 4.0]], np.float32)
    upd = np.array([[99.0]], np.float32)
    pos = np.array([-1, -1, 0, -1], np.int32)
    out = np.asarray(grid_update_pallas(state, upd, pos, interpret=True))
    np.testing.assert_array_equal(out, [[1.0, np.nan, 99.0, 4.0]])


def test_grid_update_ops_semantics():
    state = np.array([[2.0, np.nan, 5.0]], np.float32)
    upd = np.array([[3.0, 1.0, np.nan]], np.float32)
    pos = np.array([0, 1, 2], np.int32)
    out_set = np.asarray(grid_update_pallas(state, upd, pos, op="set",
                                            interpret=True))
    np.testing.assert_array_equal(out_set, upd)
    out_add = np.asarray(grid_update_pallas(state, upd, pos, op="add",
                                            interpret=True))
    np.testing.assert_array_equal(out_add, [[5.0, np.nan, np.nan]])
    # fmax: NaN only where *both* sides are NaN
    out_max = np.asarray(grid_update_pallas(state, upd, pos, op="max",
                                            interpret=True))
    np.testing.assert_array_equal(out_max, [[3.0, 1.0, 5.0]])


def test_grid_update_empty_axes_match_ref():
    """T=0, C=0 and M=0 (no touched cells) must not crash the tiler and
    must return the state unchanged, like the oracle."""
    state = np.ones((2, 4), np.float32)
    out = np.asarray(grid_update_pallas(
        state, np.empty((2, 0), np.float32), np.full(4, -1, np.int32),
        interpret=True))
    np.testing.assert_array_equal(out, state)
    out = np.asarray(grid_update_pallas(
        np.empty((0, 4), np.float32), np.empty((0, 2), np.float32),
        np.array([0, -1, 1, -1], np.int32), interpret=True))
    assert out.shape == (0, 4)
    out = np.asarray(grid_update_pallas(
        np.empty((2, 0), np.float32), np.empty((2, 3), np.float32),
        np.empty((0,), np.int32), interpret=True))
    assert out.shape == (2, 0)


def test_grid_update_rejects_unknown_op():
    state = np.ones((1, 2), np.float32)
    with pytest.raises(ValueError, match="unknown grid_update op"):
        grid_update_pallas(state, state, np.zeros(2, np.int32), op="mul",
                           interpret=True)
    with pytest.raises(ValueError, match="unknown grid_update op"):
        ref.grid_update(state, state, np.zeros(2, np.int32), op="mul")


# ---------------------------------------------------------------------------
# zr_accum
# ---------------------------------------------------------------------------

@given(
    t=st.integers(1, 12),
    a=st.integers(2, 40),
    r=st.integers(2, 300),
    seed=st.integers(0, 999),
)
@settings(max_examples=20, deadline=None)
def test_zr_accum_matches_ref(t, a, r, seed):
    rng = np.random.default_rng(seed)
    dbz = _radar_field(rng, t, a, r)
    dt_s = rng.uniform(200.0, 400.0, size=(t,)).astype(np.float32)
    got = zr_accum_pallas(dbz, dt_s, vmem_budget=256 * 1024, interpret=True)
    want = jax.jit(ref.zr_accum)(dbz, dt_s)
    np.testing.assert_array_equal(np.asarray(got), np.asarray(want))


@pytest.mark.parametrize("t", [7, 12])
def test_zr_accum_window_longer_than_time_tile(t):
    """A window that does not fit one time tile is padded (NaN dBZ, zero
    weight) and summed tile by tile: only the summation order changes."""
    rng = np.random.default_rng(t)
    dbz = _radar_field(rng, t, 20, 150)
    dt_s = rng.uniform(200.0, 400.0, size=(t,)).astype(np.float32)
    got = zr_accum_pallas(dbz, dt_s, vmem_budget=64 * 1024, interpret=True)
    want = ref.zr_accum(dbz, dt_s)
    np.testing.assert_allclose(got, want, rtol=1e-5, atol=1e-6)


def test_zr_accum_zero_below_threshold():
    dbz = np.full((3, 4, 8), -5.0, dtype=np.float32)
    out = zr_accum_pallas(dbz, np.full(3, 300.0, np.float32), interpret=True)
    np.testing.assert_array_equal(np.asarray(out), 0.0)


def test_zr_accum_known_value():
    """40 dBZ for one hour under Marshall-Palmer ≈ 11.53 mm."""
    dbz = np.full((1, 1, 1), 40.0, dtype=np.float32)
    out = zr_accum_pallas(dbz, np.array([3600.0], np.float32), interpret=True)
    expected = (1e4 / 200.0) ** (1 / 1.6)
    np.testing.assert_allclose(np.asarray(out)[0, 0], expected, rtol=1e-4)


# ---------------------------------------------------------------------------
# flash_attention
# ---------------------------------------------------------------------------

@given(
    b=st.integers(1, 2),
    hkv=st.sampled_from([1, 2, 4]),
    group=st.sampled_from([1, 2, 4]),
    sq=st.integers(1, 130),
    skv_extra=st.integers(0, 140),
    d=st.sampled_from([16, 64]),
    causal=st.booleans(),
    seed=st.integers(0, 99),
)
@settings(max_examples=25, deadline=None)
def test_flash_attention_matches_ref(b, hkv, group, sq, skv_extra, d, causal,
                                     seed):
    rng = np.random.default_rng(seed)
    hq = hkv * group
    skv = sq + skv_extra  # decode-style: queries align to the sequence end
    q = rng.normal(size=(b, hq, sq, d)).astype(np.float32)
    k = rng.normal(size=(b, hkv, skv, d)).astype(np.float32)
    v = rng.normal(size=(b, hkv, skv, d)).astype(np.float32)
    got = flash_attention_pallas(q, k, v, causal=causal, bq=64, bk=64,
                                 interpret=True)
    want = ref.flash_attention(q, k, v, causal=causal)
    np.testing.assert_allclose(got, want, rtol=2e-4, atol=2e-4)


def test_flash_attention_bf16():
    rng = np.random.default_rng(3)
    q = jnp.asarray(rng.normal(size=(1, 4, 256, 64)), dtype=jnp.bfloat16)
    k = jnp.asarray(rng.normal(size=(1, 2, 256, 64)), dtype=jnp.bfloat16)
    v = jnp.asarray(rng.normal(size=(1, 2, 256, 64)), dtype=jnp.bfloat16)
    got = flash_attention_pallas(q, k, v, causal=True, interpret=True)
    want = ref.flash_attention(q, k, v, causal=True)
    np.testing.assert_allclose(
        np.asarray(got, np.float32), np.asarray(want, np.float32),
        rtol=5e-2, atol=5e-2,
    )


def test_flash_attention_decode_single_query():
    """Sq=1 against a long cache — the serve_step hot path."""
    rng = np.random.default_rng(5)
    q = rng.normal(size=(2, 8, 1, 64)).astype(np.float32)
    k = rng.normal(size=(2, 2, 700, 64)).astype(np.float32)
    v = rng.normal(size=(2, 2, 700, 64)).astype(np.float32)
    got = flash_attention_pallas(q, k, v, causal=True, interpret=True)
    want = ref.flash_attention(q, k, v, causal=True)
    np.testing.assert_allclose(got, want, rtol=2e-4, atol=2e-4)


# ---------------------------------------------------------------------------
# mamba2_scan
# ---------------------------------------------------------------------------

@given(
    b=st.integers(1, 2),
    l=st.integers(1, 200),
    h=st.sampled_from([1, 2, 4]),
    p=st.sampled_from([8, 16]),
    n=st.sampled_from([8, 16]),
    seed=st.integers(0, 99),
)
@settings(max_examples=20, deadline=None)
def test_mamba2_scan_matches_ref(b, l, h, p, n, seed):
    rng = np.random.default_rng(seed)
    x = rng.normal(size=(b, l, h, p)).astype(np.float32)
    dt = rng.uniform(0.001, 0.1, size=(b, l, h)).astype(np.float32)
    A = -rng.uniform(0.5, 4.0, size=(h,)).astype(np.float32)
    Bm = rng.normal(size=(b, l, n)).astype(np.float32)
    Cm = rng.normal(size=(b, l, n)).astype(np.float32)
    y_got, h_got = mamba2_scan_pallas(x, dt, A, Bm, Cm, cs=64, interpret=True)
    y_want, h_want = ref.mamba2_scan(x, dt, A, Bm, Cm)
    np.testing.assert_allclose(y_got, y_want, rtol=2e-4, atol=2e-4)
    np.testing.assert_allclose(h_got, h_want, rtol=2e-4, atol=2e-4)


def test_mamba2_scan_state_continuation():
    """Scanning [first half] then [second half with h0] == full scan."""
    rng = np.random.default_rng(11)
    b, l, h, p, n = 1, 64, 2, 8, 8
    x = rng.normal(size=(b, l, h, p)).astype(np.float32)
    dt = rng.uniform(0.001, 0.1, size=(b, l, h)).astype(np.float32)
    A = -rng.uniform(0.5, 4.0, size=(h,)).astype(np.float32)
    Bm = rng.normal(size=(b, l, n)).astype(np.float32)
    Cm = rng.normal(size=(b, l, n)).astype(np.float32)
    y_full, h_full = ref.mamba2_scan(x, dt, A, Bm, Cm)
    half = l // 2
    y1, h1 = ref.mamba2_scan(x[:, :half], dt[:, :half], A, Bm[:, :half],
                             Cm[:, :half])
    y2, h2 = ref.mamba2_scan(x[:, half:], dt[:, half:], A, Bm[:, half:],
                             Cm[:, half:], h0=h1)
    np.testing.assert_allclose(
        np.concatenate([y1, y2], axis=1), y_full, rtol=1e-5, atol=1e-5
    )
    np.testing.assert_allclose(h2, h_full, rtol=1e-5, atol=1e-5)
