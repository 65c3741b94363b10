"""The band-stage back-transform's WY factors, formed on the device.

``bt_band_hh._factors`` regroups the chase's compact reflectors into the
per-group V windows and taus on the device.  The reference here is the
plain host build it replaced: a Python schedule of (column, slot) pairs
and one copy per reflector.  The two must agree bit for bit; the
back-transform through them must equal an explicit dense Q2.
"""
import jax
import numpy as np
import pytest

from dlaf_tpu.algorithms import bt_band_hh
from dlaf_tpu.comm.grid import Grid
from dlaf_tpu.common.index import Size2D
from dlaf_tpu.matrix.matrix import DistributedMatrix


def _loop_schedule(n, b, g):
    """Groups in application order, each (window row, [(col, slot), ...])
    with ``col`` the reflector's head row inside the window."""
    nsweeps = n - 2
    counts = [(n - 3 - s) // b + 1 for s in range(nsweeps)]
    offs = np.concatenate([[0], np.cumsum(counts)])
    w = b + g - 1
    n_pad = max(n, w)
    groups = []
    for j0 in range(((nsweeps - 1) // g) * g, -1, -g):
        for m in range((n - 3 - j0) // b + 1):
            base = 1 + j0 + m * b
            base_s = min(base, n_pad - w)
            cols = [
                (base - base_s + (s - j0), int(offs[s]) + m)
                for s in range(j0, min(j0 + g, nsweeps))
                if 1 + s + m * b <= n - 2
            ]
            groups.append((base_s, cols))
    return groups, w


def _loop_factors(v_refl, taus, n, b, g, dtype):
    """One reflector at a time into zeroed windows (tau 1 = identity pad)."""
    groups, w = _loop_schedule(n, b, g)
    V_all = np.zeros((len(groups), w, g), dtype)
    tau_all = np.ones((len(groups), g), dtype)
    offs = np.zeros(len(groups), np.int32)
    for gi, (base_s, cols) in enumerate(groups):
        offs[gi] = base_s
        for ci, (row_off, slot) in enumerate(cols):
            if taus[slot] == 0:
                continue
            L = min(b, w - row_off)
            V_all[gi, row_off : row_off + L, ci] = v_refl[slot, :L]
            tau_all[gi, ci] = taus[slot]
    return w, V_all, tau_all, offs


def _n_refl(n, b):
    return sum((n - 3 - s) // b + 1 for s in range(n - 2))


def _random(shape, dtype, rng):
    x = rng.standard_normal(shape)
    if np.dtype(dtype).kind == "c":
        x = x + 1j * rng.standard_normal(shape)
    return x.astype(dtype)


# (n, b, g): n not a multiple of b; g below, at and above b; n < w clamps
# every window to row 0
SHAPES = [
    (3, 2, 1), (37, 2, 1), (37, 2, 2), (37, 2, 4),
    (41, 3, 1), (41, 3, 3), (41, 3, 4),
    (70, 32, 1), (70, 32, 4), (70, 32, 32),
    (20, 32, 32), (5, 3, 4),
]


@pytest.mark.parametrize("dtype", [np.float32, np.complex64])
@pytest.mark.parametrize("n,b,g", SHAPES)
def test_factors_match_loop_reference(n, b, g, dtype):
    """Random rows (nonzero past every reflector's end, so the window's
    truncation shows) with a fifth of the taus zero."""
    rng = np.random.default_rng(n * 1000 + b * 10 + g)
    R = _n_refl(n, b)
    v = _random((R, b), dtype, rng)
    taus = _random(R, dtype, rng)
    taus[rng.random(R) < 0.2] = 0
    w_ref, V_ref, tau_ref, offs_ref = _loop_factors(v, taus, n, b, g, dtype)
    w, G, (V_all, tau_all, offs) = bt_band_hh._factors(v, taus, n, b, g, dtype)
    assert (w, G) == (w_ref, V_ref.shape[0])
    for got, want in ((V_all, V_ref), (tau_all, tau_ref), (offs, offs_ref)):
        got = np.asarray(got)
        assert got.dtype == want.dtype and got.shape == want.shape
        assert got.tobytes() == want.tobytes()


def _chase_like(n, b, dtype, rng):
    """Reflectors as the chase leaves them: v[0] = 1, zero past the
    matrix's edge, tau = 2/|v|^2 (unitary), some tau = 0."""
    R = _n_refl(n, b)
    v = _random((R, b), dtype, rng)
    taus = np.zeros(R, dtype)
    slot = 0
    for s in range(n - 2):
        for m in range((n - 3 - s) // b + 1):
            L = min(b, n - (1 + s + m * b))
            v[slot, 0] = 1
            v[slot, L:] = 0
            taus[slot] = 2 / np.sum(np.abs(v[slot]) ** 2)
            slot += 1
    taus[rng.random(R) < 0.15] = 0
    return v, taus


def _dense_q2(v, taus, n, b):
    """Q2 = H_1 H_2 ... H_R in generation order, in double precision."""
    q = np.eye(n, dtype=np.complex128)
    slot = 0
    for s in range(n - 2):
        for m in range((n - 3 - s) // b + 1):
            head = 1 + s + m * b
            L = min(b, n - head)
            x = v[slot, :L].astype(np.complex128)
            cols = q[:, head : head + L]
            q[:, head : head + L] = cols - taus[slot] * np.outer(cols @ x, x.conj())
            slot += 1
    return q


@pytest.mark.parametrize("dtype", [np.float32, np.complex64])
@pytest.mark.parametrize("path", ["dist", "host"])
@pytest.mark.parametrize("grid_shape", [(1, 1), (2, 2)])
@pytest.mark.parametrize("n,b,g", [(45, 4, 3), (37, 3, 3), (5, 4, 4)])
def test_q2_apply_matches_dense(n, b, g, grid_shape, path, dtype):
    """E <- Q2 diag(phases) E through the device-formed factors equals the
    explicit product, on one device and on a 2x2 mesh."""
    rng = np.random.default_rng(n + b)
    grid = Grid.create(Size2D(*grid_shape), jax.devices()[: grid_shape[0] * grid_shape[1]])
    v, taus = _chase_like(n, b, dtype, rng)
    k = 7
    e = _random((n, k), dtype, rng)
    phases = np.ones(n, dtype)
    if np.dtype(dtype).kind == "c":
        phases = np.exp(1j * rng.uniform(0, 2 * np.pi, n)).astype(dtype)
    hh = (None, None, phases, v, taus, b)
    if path == "dist":
        mat = DistributedMatrix.from_global(grid, e, (4, 4))
        got = bt_band_hh.bt_band_to_tridiagonal_hh_dist(hh, mat, group_size=g).to_global()
    else:
        got = bt_band_hh.bt_band_to_tridiagonal_hh(hh, e, grid, (4, 4), group_size=g).to_global()
    want = _dense_q2(v, taus, n, b) @ (phases[:, None] * e.astype(np.complex128))
    np.testing.assert_allclose(got, want, rtol=0, atol=2e-5 * np.abs(want).max())
