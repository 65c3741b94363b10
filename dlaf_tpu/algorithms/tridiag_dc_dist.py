"""Distributed Cuppen divide & conquer for the symmetric tridiagonal
eigenproblem — multi-level merges over the 2D device grid.

TPU-native re-design of the reference distributed tridiag solver
(reference: include/dlaf/eigensolver/tridiag_solver/impl.h:199+ distributed
``TridiagSolver::call``, merge.h:1810-1950 ``mergeDistSubproblems``,
merge.h:1269 ``solveRank1ProblemDist``, rot.h:158 Givens column rotations).
The reference runs per-eigenvalue laed4 worker tasks, applies deflation
Givens rotations to distributed eigenvector columns one pair at a time, and
assembles eigenvectors with distributed sub-range GEMMs.  Here every one of
those steps is re-expressed in closed form so a merge LEVEL (all merges of
one size) is a constant number of jitted SPMD calls:

  * The deflation rotation chain has STATIC structure: whether adjacent
    sorted poles rotate depends only on the pole gaps and the tiny-z mask,
    never on scan state (a rotation clears its LEFT index only, which later
    steps never re-read).  The rotation angles therefore have closed forms
    via segmented prefix sums of z^2, and the accumulated rotation matrix G
    is upper Hessenberg with entries

        G[r, j] = c_j * c_{r-1} * prod_{l=r..j-1} s_l          (r <= j)
        G[j+1, j] = -s_j

    computable per element from prefix log-sums — no sequential scan, no
    materialized G.
  * The secular equation is solved by vectorized bisection in the anchored
    (nearest-pole) representation, root-sharded over the whole device mesh
    and all_gathered (replaces the reference's nworkers laed4 tasks).  Each
    root's pole window is its merge block's row of the [B, S] poles, moved
    as a whole block row, never gathered one element at a time.
  * The rank-1 eigenvector basis U is elementwise in O(s) replicated
    vectors (zhat, poles, anchors, offsets, column norms) via the Loewner
    z-recomputation, evaluated in log-space (interlacing makes every
    ratio positive, so no sign bookkeeping).

Eigenvector assembly then becomes ONE block-diagonal-restricted SUMMA GEMM
per level with a *generated* right operand: each rank materializes only the
operand tiles it consumes, from the replicated O(n) vectors.  No O(n^2)
host, replicated, or gathered object exists anywhere — the only O(n^2)
state is the block-cyclically sharded eigenvector matrix itself.  When a
level performs no closeness rotations (G = I — the common case), the sort
permutation folds into the operand's row indexing and the level is a single
GEMM; rotation levels run two (Q <- (Q P G) U).  The GEMM contraction is
restricted to the merging sub-block (the reference's sub-range
``GeneralSub::callNN``, multiplication/general/api.h:28), and the first
pass additionally restricts rows to the pre-merge half-blocks where Q is
supported, so the level cost is ~4 n s^2 / P flops instead of dense n^3.

Leaves are the tile-aligned diagonal blocks, solved on the host by LAPACK
(``stemr`` in f64) as the reference does (tridiag_solver ``solveLeaf``),
and placed sharded over the flat device mesh.  All subproblem sizes are
powers of two times the leaf (padding poles are decoupled, larger than any
true eigenvalue, and deflate to identity columns automatically), so every level is one static shape.
"""
from __future__ import annotations

from functools import partial
from typing import Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax
from jax.sharding import NamedSharding, PartitionSpec as P

from dlaf_tpu.comm import collectives as coll
from dlaf_tpu.comm.grid import COL_AXIS, ROW_AXIS, Grid
from dlaf_tpu.matrix.distribution import Distribution
from dlaf_tpu.matrix.matrix import DistributedMatrix, place
from dlaf_tpu.obs.trace import phase as _phase
from dlaf_tpu.obs.trace import scope as _scope
from dlaf_tpu.plan import core as _plancache

_BOTH = (ROW_AXIS, COL_AXIS)


def _spmd(op, grid, fn, in_specs, out_specs, donate=()):
    sm = jax.shard_map(
        fn, mesh=grid.mesh, in_specs=in_specs, out_specs=out_specs, check_vma=False
    )
    return _plancache.jit(op, sm, donate_argnums=donate)


def _plan(n: int, nb: int, leaf_target: int):
    """Leaf size s0 (multiple of nb), level count L, padded size n_pad with
    n_pad = s0 * 2^L >= n."""
    leaf_target = max(nb, leaf_target)
    nleaf_t = max(1, -(-n // leaf_target))
    L = max(0, (nleaf_t - 1).bit_length())
    s0 = -(-n // ((1 << L) * nb)) * nb
    return s0, L, s0 << L


# --------------------------------------------------------------------------
# leaf stage: host LAPACK eigensolves of the tile-aligned diagonal blocks,
# placed into the sharded eigenvector matrix
# --------------------------------------------------------------------------


def _leaf_eigh(d_mod, e_pad, s0: int, nleaf: int, nslots: int, rdt):
    """Leaf eigenpairs on the host: eigenvalues [n_pad] and eigenvector
    blocks [nslots, s0, s0] (slots past ``nleaf`` stay zero).  XLA's TPU
    ``eigh`` missed 512x512 leaf eigenvalues by up to 3.4e-3 ||T|| in f32
    (PR 21 chip run); LAPACK in f64 is exact to f32 rounding."""
    import scipy.linalg as sla

    lam = np.empty(nleaf * s0, rdt)
    q = np.zeros((nslots, s0, s0), rdt)
    for b in range(nleaf):
        sl = slice(b * s0, (b + 1) * s0)
        w, v = sla.eigh_tridiagonal(
            d_mod[sl].astype(np.float64), e_pad[sl][:-1].astype(np.float64)
        )
        lam[sl], q[b] = w, v
    return lam, q


def _leaf_kernel(qL, *, g, s0, nleaf, nloc, dt):
    """Place this device's leaf eigenvector blocks ``qL`` [nloc, s0, s0]
    (leaves ``flat * nloc + i``) into the stacked eigenvector matrix."""
    myr, myc = coll.my_rank()

    # eigenvectors -> stacked block-cyclic tiles: ONE all_gather round per
    # local leaf slot (nloc = nleaf/P rounds total, not nleaf sequential
    # collectives), then communication-free local placement of the P
    # gathered leaves
    t0t = s0 // g.nb
    P_ = g.pr * g.pc
    gi = jnp.arange(g.ltr) * g.pr + myr
    gj = jnp.arange(g.ltc) * g.pc + myc

    def place_leaf(b2, qb, x):
        qt = qb.reshape(t0t, g.nb, t0t, g.nb).transpose(0, 2, 1, 3)
        ri = gi - b2 * t0t
        cj = gj - b2 * t0t
        mask = (
            ((ri >= 0) & (ri < t0t))[:, None] & ((cj >= 0) & (cj < t0t))[None, :]
        ) & (b2 < nleaf)
        sel = qt[jnp.clip(ri, 0, t0t - 1)][:, jnp.clip(cj, 0, t0t - 1)]
        return x + jnp.where(mask[:, :, None, None], sel, jnp.zeros_like(sel))

    def putq_round(lb2, x):
        qsel = lax.dynamic_index_in_dim(qL, lb2, 0, keepdims=False)
        qg = lax.all_gather(qsel, _BOTH)  # [P, s0, s0]

        def inner(q, x):
            return place_leaf(q * nloc + lb2, qg[q], x)

        return lax.fori_loop(0, P_, inner, x)

    x = lax.fori_loop(0, nloc, putq_round, jnp.zeros((g.ltr, g.ltc, g.nb, g.nb), dt))
    return coll.relocal(x)


# --------------------------------------------------------------------------
# per-level merge parameters: z extraction + deflation + secular solve
# --------------------------------------------------------------------------


def _block_rows(v, bq, S):
    """The [RPD, S] window whose row i is merge block ``bq[i]`` of ``v``:
    ``v[bq[:, None] * S + arange(S)]``, as one gather of whole block rows
    (never S single elements per root)."""
    return v.reshape(-1, S)[bq]


def _params_kernel(x, lam_prev, beta, *, g, S, B, n_pad, RPD, iters, dt):
    x = coll.local(x)
    myr, myc = coll.my_rank()
    flat = myr * g.pc + myc
    s_half = S // 2
    tiny = jnp.finfo(dt).tiny
    tol = jnp.asarray(8.0, dt) * jnp.finfo(dt).eps
    i32 = jnp.int32

    # --- z extraction: z[j] = Q[r1(blk), j] + sgn * Q[r2(blk), j] ----------
    gi = jnp.arange(g.ltr) * g.pr + myr
    gj = jnp.arange(g.ltc) * g.pc + myc
    ge_row = gi[:, None] * g.nb + jnp.arange(g.nb)[None, :]  # [ltr, nb]
    ge_col = gj[:, None] * g.nb + jnp.arange(g.nb)[None, :]  # [ltc, nb]
    blk_col = ge_col // S
    r1 = blk_col * S + (s_half - 1)
    sgn = jnp.sign(jnp.where(beta == 0, jnp.ones_like(beta), beta))
    sgn_col = sgn[jnp.clip(blk_col, 0, B - 1)]
    m1 = ge_row[:, None, :, None] == r1[None, :, None, :]
    m2 = ge_row[:, None, :, None] == (r1 + 1)[None, :, None, :]
    w = m1.astype(dt) + sgn_col[None, :, None, :] * m2.astype(dt)
    with _scope("dc.z_extract"):
        zpart = jnp.sum(x * w, axis=(0, 2))  # [ltc, nb]
        z_loc = jnp.zeros((n_pad,), dt).at[ge_col.reshape(-1)].add(zpart.reshape(-1))
        z = lax.psum(z_loc, _BOTH)

    # --- per-block sort + deflation (all closed-form, [B, S]) --------------
    d_blk = lam_prev.reshape(B, S)
    z_blk = z.reshape(B, S)
    ord1 = jnp.argsort(d_blk, axis=1)
    io = jnp.argsort(ord1, axis=1).astype(i32)  # inverse permutation
    ds = jnp.take_along_axis(d_blk, ord1, 1)
    zs = jnp.take_along_axis(z_blk, ord1, 1)
    rho = jnp.abs(beta)  # [B]
    zn2 = jnp.sum(zs * zs, axis=1)
    keep0 = jnp.abs(zs) * jnp.sqrt(rho)[:, None] > tol * jnp.sqrt(zn2 + tiny)[:, None]
    # norm-RELATIVE spread (no absolute constant: accuracy must be invariant
    # under scaling of the input matrix, like LAPACK dlaed2's tolerance)
    span = jnp.max(jnp.abs(ds), axis=1) + rho * zn2
    tol_gap = (tol * span)[:, None]
    close = jnp.concatenate(
        [
            (ds[:, 1:] - ds[:, :-1] < tol_gap) & keep0[:, :-1] & keep0[:, 1:],
            jnp.zeros((B, 1), bool),
        ],
        1,
    )
    idx = jnp.broadcast_to(jnp.arange(S)[None, :], (B, S))
    break_before = jnp.concatenate([jnp.ones((B, 1), bool), ~close[:, :-1]], 1)
    sid = lax.cummax(jnp.where(break_before, idx, 0), axis=1)
    z2m = jnp.where(keep0, zs * zs, 0.0)

    # run-local prefix norms pn[j] = sqrt(sum of z^2 over the rotation run
    # through j).  A global-cumsum difference catastrophically cancels when a
    # run's z values are far below the block's total ||z||^2 (clustered
    # spectra), so use a segmented scan that resets at run starts.
    def _seg_comb(a, b):
        xa, fa = a
        xb, fb = b
        return jnp.where(fb, xb, xa + xb), fa | fb

    pn2, _ = lax.associative_scan(_seg_comb, (z2m, break_before), axis=1)
    pn = jnp.sqrt(jnp.maximum(pn2, 0.0))
    rsafe = jnp.maximum(jnp.concatenate([pn[:, 1:], jnp.ones((B, 1), dt)], 1), tiny)
    carr = jnp.where(
        close, jnp.concatenate([zs[:, 1:], jnp.zeros((B, 1), dt)], 1) / rsafe, 1.0
    )
    run_start = sid == idx
    pn_signed = jnp.where(run_start, jnp.where(keep0, zs, 0.0), pn)
    sarr = jnp.where(close, pn_signed / rsafe, 0.0)
    run_end = jnp.concatenate([jnp.zeros((B, 1), bool), close[:, :-1]], 1)
    zpost = jnp.where(close, 0.0, jnp.where(run_end, pn, jnp.where(keep0, zs, 0.0)))
    keep = keep0 & ~close
    # exclusive prefix arrays for G products prod_{l=r..j-1} s_l
    logs = jnp.where(close, jnp.log(jnp.maximum(jnp.abs(sarr), tiny)), 0.0)
    Cx = jnp.concatenate([jnp.zeros((B, 1), dt), jnp.cumsum(logs, 1)[:, :-1]], 1)
    Zx = jnp.concatenate(
        [jnp.zeros((B, 1), i32), jnp.cumsum((~close).astype(i32), 1)[:, :-1]], 1
    )
    NCx = jnp.concatenate(
        [jnp.zeros((B, 1), i32), jnp.cumsum((close & (sarr < 0)).astype(i32), 1)[:, :-1]],
        1,
    )
    has_rot = jnp.any(close)

    # --- secular solve, root-sharded over the flat mesh --------------------
    ds_flat = ds.reshape(-1)
    keep_flat = keep.reshape(-1)
    z2_flat = jnp.where(keep, zpost * zpost, 0.0).reshape(-1)
    pos = jnp.clip(flat * RPD + jnp.arange(RPD), 0, n_pad - 1)
    bq = pos // S
    rows = partial(_block_rows, bq=bq, S=S)
    j_blk = jnp.arange(S)[None, :]  # window column = index within the block
    dw = rows(ds_flat)  # [RPD, S]
    z2w = rows(z2_flat)
    rho_q = rho[bq]
    # next active pole / per-block upper bound
    maskedd = jnp.where(keep, ds, jnp.inf)
    rev = jnp.flip(lax.cummin(jnp.flip(maskedd, 1), axis=1), 1)
    nxt = jnp.concatenate([rev[:, 1:], jnp.full((B, 1), jnp.inf, dt)], 1)
    any_keep = jnp.any(keep, axis=1)
    # strict upper root bracket, norm-relative slack (f(upper) > 0 for any
    # positive slack; tiny guards the all-zero block)
    eps4 = jnp.asarray(4.0, dt) * jnp.finfo(dt).eps
    upper_b = jnp.where(
        any_keep,
        jnp.max(jnp.where(keep, ds, -jnp.inf), axis=1)
        + rho * zn2 * (1.0 + eps4)
        + eps4 * span
        + tiny,
        0.0,
    )
    d_next = jnp.where(jnp.isfinite(nxt), nxt, upper_b[:, None])
    gap = d_next - ds
    d_q = ds_flat[pos]
    d_next_q = d_next.reshape(-1)[pos]
    gap_q = gap.reshape(-1)[pos]

    # tune.dc_secular_pallas: fused VMEM bisection (pole tables read from
    # HBM once instead of once per round); bit-matches the XLA loop below.
    # f32 only (TPU Pallas has no f64); interpret-mode on CPU backends so
    # the wiring stays testable off-hardware.
    from dlaf_tpu.tune import get_tune_parameters as _gtp

    use_pallas_secular = bool(
        getattr(_gtp(), "dc_secular_pallas", False) and dt == jnp.dtype(jnp.float32)
    )

    def bisect(anchor_vec, lo0, hi0):
        if use_pallas_secular:
            import jax as _jax

            from dlaf_tpu.ops.pallas_secular import secular_bisect

            return secular_bisect(
                dw, z2w, rho_q, anchor_vec, lo0, hi0, iters,
                _jax.default_backend() == "cpu",
            )
        ag = dw - anchor_vec[:, None]

        def body(_, lh):
            lo, hi = lh
            mid = 0.5 * (lo + hi)
            diff = ag - mid[:, None]
            safe = jnp.where(diff == 0, tiny, diff)
            fm = 1.0 + rho_q * jnp.sum(z2w / safe, axis=1)
            return jnp.where(fm < 0, mid, lo), jnp.where(fm < 0, hi, mid)

        lo, hi = lax.fori_loop(0, iters, body, (lo0, hi0))
        return 0.5 * (lo + hi)

    mu = bisect(d_q, jnp.zeros_like(d_q), gap_q)
    nu = bisect(d_next_q, -gap_q, jnp.zeros_like(d_q))
    use_r = jnp.abs(nu) < jnp.abs(mu)
    anchor_q = jnp.where(use_r, d_next_q, d_q)
    kq = keep_flat[pos]
    off_q = jnp.where(kq, jnp.where(use_r, nu, mu), 0.0)

    # fixed-point refinement of the anchor pole's own term (LAPACK laed4's
    # relative accuracy near poles, where linear bisection bottoms out at
    # ABSOLUTE bracket precision but zhat needs RELATIVE accuracy in off):
    # 0 = 1 + R + rho z_a^2/(-off)  =>  off = rho z_a^2 / (1 + R)
    idx_flat = jnp.broadcast_to(jnp.arange(S, dtype=jnp.int32)[None, :], (B, S))
    big_i = jnp.int32(S)
    midx = jnp.where(keep, idx_flat, big_i)
    rev_i = jnp.flip(lax.cummin(jnp.flip(midx, 1), axis=1), 1)
    nxt_i = jnp.concatenate([rev_i[:, 1:], jnp.full((B, 1), big_i, jnp.int32)], 1)
    na_loc = jnp.clip(nxt_i.reshape(-1)[pos], 0, S - 1)  # next-active local idx
    a_idx = jnp.where(use_r, bq * S + na_loc, pos)
    z2a = z2_flat[a_idx]
    lo_g = jnp.where(use_r, -gap_q, jnp.zeros_like(gap_q))
    hi_g = jnp.where(use_r, jnp.zeros_like(gap_q), gap_q)
    ag_r = dw - anchor_q[:, None]
    own_sel = j_blk == (a_idx - bq * S)[:, None]

    # only roots at/below the bisection resolution floor need (and safely
    # admit) the fixed-point; larger offsets already have the relative
    # accuracy zhat requires
    floor = gap_q * jnp.asarray(2.0 ** (-(iters - 6)), dt)

    def refine(_, off):
        diff = ag_r - off[:, None]
        safe = jnp.where(diff == 0, tiny, diff)
        rest = rho_q * jnp.sum(jnp.where(own_sel, 0.0, z2w / safe), axis=1)
        denom = 1.0 + rest
        cand = rho_q * z2a / jnp.where(denom == 0, tiny, denom)
        near_pole = (jnp.abs(off) <= floor) | (jnp.abs(cand) <= floor)
        good = jnp.isfinite(cand) & (cand > lo_g) & (cand < hi_g) & near_pole
        return jnp.where(good, cand, off)

    off_q = jnp.where(kq, lax.fori_loop(0, 3, refine, off_q), 0.0)
    lam_q = jnp.where(kq, anchor_q + off_q, d_q)

    def gather_flat(v):
        out = lax.all_gather(v, _BOTH, tiled=True)
        return out[:n_pad]

    anchor = gather_flat(anchor_q)
    off = gather_flat(off_q)
    lam = gather_flat(lam_q)

    # --- zhat via Loewner formula in log space (shard over j) --------------
    aw = rows(anchor)
    ow = rows(off)
    kw = rows(keep_flat)
    numw = (aw - d_q[:, None]) + ow
    denw = dw - d_q[:, None]
    act = kw & kq[:, None] & (j_blk != (pos - bq * S)[:, None])
    logratio = jnp.where(
        act,
        jnp.log(jnp.maximum(jnp.abs(numw), tiny))
        - jnp.log(jnp.maximum(jnp.abs(denw), tiny)),
        0.0,
    )
    own_q = (anchor - ds_flat)[pos] + off[pos]
    lzh2 = (
        jnp.log(jnp.maximum(own_q, tiny))
        - jnp.log(jnp.maximum(rho_q, tiny))
        + jnp.sum(logratio, axis=1)
    )
    zpost_flat = zpost.reshape(-1)
    sgn_z = jnp.where(zpost_flat[pos] < 0, -1.0, 1.0).astype(dt)
    zhat_q = jnp.where(kq, sgn_z * jnp.exp(0.5 * lzh2), 0.0)
    zhat = gather_flat(zhat_q)

    # --- column norms of U (shard over columns t) ---------------------------
    zh2w = rows(zhat * zhat)
    numw2 = (anchor[pos][:, None] - dw) + off[pos][:, None]
    safe2 = jnp.where(numw2 == 0, tiny, numw2)
    nsum = jnp.sum(jnp.where(kw, zh2w / (safe2 * safe2), 0.0), axis=1)
    norm_q = jnp.where(kq & (nsum > 0), jnp.sqrt(nsum), 1.0)
    norms = gather_flat(norm_q)

    # --- final per-block ordering ------------------------------------------
    lam_blk = lam.reshape(B, S)
    ord2 = jnp.argsort(lam_blk, axis=1).astype(i32)
    lam_sorted = jnp.take_along_axis(lam_blk, ord2, 1).reshape(-1)

    pack = (
        lam_sorted,
        ds_flat,
        zhat,
        anchor,
        off,
        norms,
        keep_flat,
        ord2.reshape(-1),
        io.reshape(-1),
        carr.reshape(-1),
        sarr.reshape(-1),
        close.reshape(-1),
        Cx.reshape(-1),
        Zx.reshape(-1),
        NCx.reshape(-1),
        has_rot,
    )
    return pack


# --------------------------------------------------------------------------
# level GEMM: Q <- Q (P G) U restricted to the merging blocks, with the
# right operands GENERATED tile-locally from the replicated vectors
# --------------------------------------------------------------------------


def _u_tile(k, b, gj_w, cmask, prm, *, g, S, B, n_pad, dt, row_remap):
    """Generated operand tile stack W[Lw, nb, nb]: the secular eigenvector
    basis U with final-order columns; ``row_remap`` folds the sort
    permutation P into the row index (G = I levels)."""
    (ds, zhat, anchor, off, norms, keep, ord2, io) = prm
    tiny = jnp.finfo(dt).tiny
    nb = g.nb
    gi_el = k * nb + jnp.arange(nb)  # [nb] global contraction element
    if row_remap:
        j_loc = io[gi_el]
    else:
        j_loc = (gi_el - b * S).astype(jnp.int32)
    j_glob = b * S + j_loc
    zh_j = zhat[j_glob]
    d_j = ds[j_glob]
    q_el = gj_w[:, None] * nb + jnp.arange(nb)[None, :]  # [Lw, nb]
    q_cl = jnp.clip(q_el, 0, n_pad - 1)
    t_loc = ord2[q_cl]
    t_glob = jnp.clip(b * S + t_loc, 0, n_pad - 1)
    an_t = anchor[t_glob]
    of_t = off[t_glob]
    no_t = norms[t_glob]
    kp_t = keep[t_glob]
    num = (an_t[:, None, :] - d_j[None, :, None]) + of_t[:, None, :]
    safe = jnp.where(num == 0, tiny, num)
    ukeep = -zh_j[None, :, None] / safe / no_t[:, None, :]
    ident = (j_loc[None, :, None] == t_loc[:, None, :]).astype(dt)
    w = jnp.where(kp_t[:, None, :], ukeep, ident)
    return jnp.where(cmask[:, None, None], w, jnp.zeros_like(w))


def _pg_tile(k, b, gj_w, cmask, prm, *, g, S, B, n_pad, dt):
    """Generated operand tile stack (P G)[Lw, nb, nb]: the accumulated
    deflation rotations with the sort permutation folded into rows.

        (P G)[i, j] = G[io[i], j],
        G[r, j] = c^_j c_{r-1} prod_{l=r..j-1} s_l   (r <= j)
                  -s_j                               (r = j+1)
    """
    (io, carr, sarr, close, Cx, Zx, NCx) = prm
    nb = g.nb
    gi_el = k * nb + jnp.arange(nb)
    r_loc = io[gi_el]  # [nb] sorted row index (local)
    r_glob = b * S + r_loc
    q_el = gj_w[:, None] * nb + jnp.arange(nb)[None, :]  # [Lw, nb]
    q_cl = jnp.clip(q_el, 0, n_pad - 1)
    jc_loc = (q_cl - b * S).astype(jnp.int32)  # sorted col index (local)
    jc_cl = jnp.clip(jc_loc, 0, S - 1)
    j_glob = jnp.clip(b * S + jc_cl, 0, n_pad - 1)
    last = jc_cl == S - 1
    ch_j = jnp.where(last, 1.0, carr[j_glob])
    sh_j = jnp.where(last, 0.0, sarr[j_glob])
    cm1 = jnp.where(
        r_loc == 0, jnp.ones((), dt), carr[jnp.clip(r_glob - 1, 0, n_pad - 1)]
    )
    # prod_{l=r..j-1} s_l via exclusive prefix sums (per block)
    Cj = Cx[j_glob]
    Cr = Cx[r_glob]
    nz = Zx[j_glob][:, None, :] - Zx[r_glob][None, :, None]
    neg = NCx[j_glob][:, None, :] - NCx[r_glob][None, :, None]
    mag = jnp.exp(Cj[:, None, :] - Cr[None, :, None])
    sign = jnp.where(neg % 2 == 0, 1.0, -1.0).astype(dt)
    prod = jnp.where(nz == 0, mag * sign, 0.0)
    r_b = r_loc[None, :, None]
    j_b = jc_cl[:, None, :]
    val = jnp.where(
        r_b == j_b + 1,
        -sh_j[:, None, :],
        jnp.where(r_b <= j_b, ch_j[:, None, :] * cm1[None, :, None] * prod, 0.0),
    )
    return jnp.where(cmask[:, None, None], val, jnp.zeros_like(val))


def _gemm_pass(x, wbuilder, *, g, B, t2, half_restrict, Lr, Lw, myr, myc):
    """One block-diagonal-restricted generated-operand SUMMA pass."""
    th = t2 // 2
    mt = g.mt
    nb = g.nb

    i32 = jnp.int32

    def body(idx, acc):
        idx = idx.astype(i32)
        b = idx // t2
        kk = idx % t2
        k = b * t2 + kk
        if half_restrict:
            row_start = b * t2 + (kk // th) * th
            span = th
        else:
            row_start = b * t2
            span = t2
        rs = jnp.clip((row_start + g.pr - 1 - myr) // g.pr, 0, max(g.ltr - Lr, 0)).astype(i32)
        gi_w = (rs + jnp.arange(Lr, dtype=i32)) * g.pr + myr
        rmask = (gi_w >= row_start) & (gi_w < row_start + span) & (gi_w < mt)
        kc = k % g.pc
        lkc = jnp.clip(k // g.pc, 0, max(g.ltc - 1, 0)).astype(i32)
        zero = jnp.zeros((), i32)
        aw = lax.dynamic_slice(x, (rs, lkc, zero, zero), (Lr, 1, nb, nb))[:, 0]
        aw = jnp.where((rmask & (myc == kc))[:, None, None], aw, jnp.zeros_like(aw))
        panel = lax.psum(aw, COL_AXIS)
        cs = jnp.clip((b * t2 + g.pc - 1 - myc) // g.pc, 0, max(g.ltc - Lw, 0)).astype(i32)
        gj_w = (cs + jnp.arange(Lw, dtype=i32)) * g.pc + myc
        cmask = (gj_w >= b * t2) & (gj_w < (b + 1) * t2) & (gj_w < mt)
        w = wbuilder(k, b, gj_w, cmask)
        contrib = jnp.einsum("iab,jbc->ijac", panel, w)
        cw = lax.dynamic_slice(acc, (rs, cs, zero, zero), (Lr, Lw, nb, nb))
        return lax.dynamic_update_slice(acc, cw + contrib, (rs, cs, zero, zero))

    return lax.fori_loop(0, B * t2, body, jnp.zeros_like(x))


def _level_kernel(x, *arrs, g, S, B, n_pad, dt, rot):
    x = coll.local(x)
    myr, myc = coll.my_rank()
    t2 = S // g.nb
    th = t2 // 2
    Lh = min(g.ltr, -(-th // g.pr))
    Lf = min(g.ltr, -(-t2 // g.pr))
    Lw = min(g.ltc, -(-t2 // g.pc))
    (ds, zhat, anchor, off, norms, keep, ord2, io, carr, sarr, close, Cx, Zx, NCx) = arrs
    uprm = (ds, zhat, anchor, off, norms, keep, ord2, io)
    kw = dict(g=g, S=S, B=B, n_pad=n_pad, dt=dt)
    if not rot:
        ub = partial(_u_tile, prm=uprm, row_remap=True, **kw)
        out = _gemm_pass(
            x, ub, g=g, B=B, t2=t2, half_restrict=True, Lr=Lh, Lw=Lw, myr=myr, myc=myc
        )
    else:
        gprm = (io, carr, sarr, close, Cx, Zx, NCx)
        gb = partial(_pg_tile, prm=gprm, **kw)
        t = _gemm_pass(
            x, gb, g=g, B=B, t2=t2, half_restrict=True, Lr=Lh, Lw=Lw, myr=myr, myc=myc
        )
        ub = partial(_u_tile, prm=uprm, row_remap=False, **kw)
        out = _gemm_pass(
            t, ub, g=g, B=B, t2=t2, half_restrict=False, Lr=Lf, Lw=Lw, myr=myr, myc=myc
        )
    return coll.relocal(out)


# --------------------------------------------------------------------------
# driver
# --------------------------------------------------------------------------

def _geometry(dist):
    from dlaf_tpu.algorithms._spmd import Geometry

    return Geometry.of(dist)


def tridiag_dc_distributed(
    grid: Grid,
    d: np.ndarray,
    e: np.ndarray,
    block_size: int,
    dtype=np.float64,
    spectrum: Optional[Tuple[int, int]] = None,
) -> Tuple[np.ndarray, DistributedMatrix]:
    """Multi-level distributed D&C.  Returns (eigenvalues ascending [host],
    eigenvector DistributedMatrix n x k over ``grid``), k = n or the
    ``spectrum`` slice width.  Eigenvectors are computed in the real dtype
    matching ``dtype`` and cast on device for complex callers."""
    from dlaf_tpu.matrix import util as mutil
    from dlaf_tpu.tune import get_tune_parameters, matmul_precision

    rdt = (
        np.float32
        if np.dtype(dtype) in (np.dtype(np.float32), np.dtype(np.complex64))
        else np.float64
    )
    d = np.asarray(d, rdt)
    e = np.asarray(e, rdt)
    n = d.shape[0]
    nb = int(block_size)
    if n == 0:
        return d, DistributedMatrix.zeros(grid, (0, 0), (nb, nb), dtype)
    leaf_target = int(getattr(get_tune_parameters(), "dc_leaf_size", 512))
    s0, L, n_pad = _plan(n, nb, leaf_target)
    Ptot = grid.grid_size.count()
    iters = 70 if rdt == np.float64 else 42

    # host prep: pad, tear all leaf boundaries at once (Cuppen, all levels).
    # Padding poles scale WITH the data (an absolute constant would inflate
    # the norm-relative deflation tolerance of blocks containing padding);
    # tiny keeps them strictly above the eigenvalues of an all-zero matrix.
    scale = float(np.max(np.abs(d)) + 2.0 * (np.max(np.abs(e)) if e.size else 0.0))
    big = 1.25 * scale + float(np.finfo(rdt).tiny)
    pad_vals = big * (2.0 + np.arange(n_pad - n, dtype=rdt) / max(1, n_pad))
    d_mod = np.concatenate([d, pad_vals])
    e_pad = np.zeros(n_pad, rdt)
    ne = min(e.shape[0], n - 1)
    e_pad[:ne] = e[:ne]
    nleaf = n_pad // s0
    for mth in range(s0, n_pad, s0):
        beta = abs(e_pad[mth - 1])
        d_mod[mth - 1] -= beta
        d_mod[mth] -= beta

    dist = Distribution((n_pad, n_pad), (nb, nb), grid.grid_size, (0, 0))
    g = _geometry(dist)
    dt = jnp.dtype(rdt)
    rep = P()
    stacked = P(ROW_AXIS, COL_AXIS)

    prec = get_tune_parameters().eigensolver_matmul_precision
    # dc_secular_pallas is baked at trace time -> must be in the compile key
    # (round-4 lesson: a knob outside the key is a dead knob)
    key0 = (
        grid.cache_key, n_pad, s0, nb, str(dt), prec,
        bool(getattr(get_tune_parameters(), "dc_secular_pallas", False)),
    )
    nloc = -(-nleaf // Ptot)

    def build_leaf():
        return _spmd(
            "dc_leaf",
            grid,
            partial(_leaf_kernel, g=g, s0=s0, nleaf=nleaf, nloc=nloc, dt=dt),
            in_specs=(P(_BOTH),),
            out_specs=stacked,
        )

    leaf_fn = _plancache.cached("dc_leaf", key0, build_leaf)
    with _phase("tridiag/leaves"):
        lam0, q0 = _leaf_eigh(d_mod, e_pad, s0, nleaf, nloc * Ptot, rdt)
    x = leaf_fn(place(q0, NamedSharding(grid.mesh, P(_BOTH))))
    lam = jnp.asarray(lam0)

    for lvl in range(L):
        S = (s0 << lvl) * 2
        B = n_pad // S
        RPD = -(-n_pad // Ptot)
        mids = np.arange(B) * S + S // 2
        beta_l = jnp.asarray(e_pad[mids - 1])
        def build_params(S=S, B=B, RPD=RPD):
            return _spmd(
                "dc_params",
                grid,
                partial(
                    _params_kernel, g=g, S=S, B=B, n_pad=n_pad, RPD=RPD,
                    iters=iters, dt=dt,
                ),
                in_specs=(stacked, rep, rep),
                out_specs=tuple([rep] * 16),
            )

        params_fn = _plancache.cached("dc_params", (lvl,) + key0, build_params)
        with matmul_precision(prec):
            prm = params_fn(x, lam, beta_l)
        lam = prm[0]
        with _phase("tridiag/readback"):
            has_rot = bool(prm[15])
        def build_gemm(S=S, B=B, has_rot=has_rot):
            return _spmd(
                "dc_gemm",
                grid,
                partial(_level_kernel, g=g, S=S, B=B, n_pad=n_pad, dt=dt, rot=has_rot),
                in_specs=tuple([stacked] + [rep] * 14),
                out_specs=stacked,
                donate=(0,),
            )

        gemm_fn = _plancache.cached("dc_gemm", (lvl, has_rot) + key0, build_gemm)
        with matmul_precision(prec):
            x = gemm_fn(x, *prm[1:15])

    with _phase("tridiag/readback"):
        w = np.asarray(lam)[:n]
    mat = DistributedMatrix(dist, grid, x)
    il, iu = (0, n - 1) if spectrum is None else spectrum
    out = mutil.sub_matrix(mat, (0, il), (n, iu - il + 1)) if (n_pad != n or spectrum is not None) else mat
    if np.dtype(dtype).kind == "c":
        cdata = out.data.astype(np.dtype(dtype))
        out = DistributedMatrix(out.dist, grid, cdata)
    return (w if spectrum is None else w[il : iu + 1]), out
