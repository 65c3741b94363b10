"""Compile the main-path Pallas kernels and the distributed Cholesky for a
described (not attached) TPU v5e, at real widths.

Nothing runs: each case lowers and compiles for ``v5e:2x2`` devices from
``jax.experimental.topologies``, which raises what the chip's compiler
would raise (Mosaic layout rules, VMEM limits, memory that does not fit).
The topology is described in a module-scoped fixture, never at import:
only one process may load libtpu, and pytest-xdist workers import every
test file.
"""
import contextlib
import re

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P, SingleDeviceSharding

HBM_BYTES = 16 * 10**9  # one v5e chip


@pytest.fixture(scope="module")
def topo():
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache as cc

    with pytest.MonkeyPatch.context() as mp:
        mp.setenv("TPU_LOG_DIR", "disabled")  # else libtpu logs under /tmp
        try:
            desc = topologies.get_topology_desc(platform="tpu", topology_name="v5e:2x2")
        except Exception as e:  # noqa: BLE001 - any failure means "cannot describe"
            pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
        # a described-device compile is written to the persistent cache but
        # cannot be read back without a chip: keep the cache off meanwhile
        enabled = jax.config.jax_enable_compilation_cache
        jax.config.update("jax_enable_compilation_cache", False)
        cc.reset_cache()
        try:
            yield desc
        finally:
            jax.config.update("jax_enable_compilation_cache", enabled)
            cc.reset_cache()


@pytest.fixture(scope="module")
def one_chip(topo):
    return SingleDeviceSharding(topo.devices[0])


@pytest.fixture(scope="module")
def mesh_2x2(topo):
    return Mesh(np.array(topo.devices).reshape(2, 2), ("r", "c"))


@contextlib.contextmanager
def _x64(on: bool):
    """The suite runs with jax_enable_x64; f32 programs on the chip run
    without it (``chip_smoke.py``), so some cases compile that way."""
    was = jax.config.jax_enable_x64
    jax.config.update("jax_enable_x64", on)
    try:
        yield
    finally:
        jax.config.update("jax_enable_x64", was)


def _shape(shape, sharding, dtype=jnp.float32):
    return jax.ShapeDtypeStruct(shape, dtype, sharding=sharding)


def _compile_on_mesh(mesh, fn, args, nout, x64: bool = True):
    """jit(shard_map(fn)) over the 2x2 mesh, every operand stacked
    ``P('r', 'c')``; ``fn`` sees the per-device block without the two
    leading grid axes."""
    spec = P("r", "c")
    sq = lambda v: v.reshape(v.shape[2:])

    def body(*blocks):
        out = fn(*(sq(b) for b in blocks))
        return tuple(o.reshape((1, 1) + o.shape) for o in out)

    shard = NamedSharding(mesh, spec)
    specs = tuple(_shape((2, 2) + s, shard, d) for s, d in args)
    f = jax.jit(jax.shard_map(body, mesh=mesh, in_specs=(spec,) * len(args),
                              out_specs=(spec,) * nout, check_vma=False))
    with _x64(x64):
        compiled = f.lower(*specs).compile()
    assert "tpu_custom_call" in compiled.as_text()
    return compiled


@pytest.mark.parametrize("nb", [256, 512])
def test_potrf_tile_compiles(one_chip, nb):
    from dlaf_tpu.ops import pallas_potrf

    jax.jit(pallas_potrf.potrf_tile).lower(_shape((nb, nb), one_chip)).compile()


@pytest.mark.parametrize("nb", [256, 512])
def test_panel_trsm_compiles(one_chip, nb):
    from dlaf_tpu.ops import pallas_panel_trsm

    jax.jit(pallas_panel_trsm.panel_trsm_right_lower_t).lower(
        _shape((nb, nb), one_chip), _shape((4 * nb, nb), one_chip)).compile()


def test_secular_bisect_compiles(one_chip):
    """K = S = 4096: the top merge of an N=4096 D&C on one chip."""
    from dlaf_tpu.ops import pallas_secular

    k = s = 4096
    tab, vec = _shape((k, s), one_chip), _shape((k,), one_chip)
    jax.jit(lambda *a: pallas_secular.secular_bisect(*a, 40)).lower(
        tab, tab, vec, vec, vec, vec).compile()


def test_sbr_chunk_has_no_gather(one_chip):
    """One SBR chunk at the N=4096 HEEV shape (b1=128, b2=32, 16 sweeps of
    32 chase steps), as ``sbr_reduce`` builds it: the window moves are
    layout ops, with no element gather on the chip."""
    from functools import partial

    from dlaf_tpu.algorithms.band_reduction import _sbr_chunk_kernel
    from dlaf_tpu.tune import matmul_precision

    b1, b2, CH, K, n_pad = 128, 32, 16, 32, 4640
    f = jax.jit(partial(_sbr_chunk_kernel, b1=b1, b2=b2, CH=CH, K=K, want_q=True),
                donate_argnums=(0, 1))
    with matmul_precision("float32"), _x64(False):
        compiled = f.lower(_shape((2 * b1, n_pad), one_chip),
                           _shape((CH, K + 1, b1, b1), one_chip),
                           _shape((), one_chip, jnp.int32)).compile()
    # match instructions: the text's stack-frame table holds this test's name
    ops = re.findall(r" (gather|scatter)\(", compiled.as_text())
    assert not ops, f"{len(ops)} gather/scatter ops in jit_sbr_chunk"


@pytest.mark.parametrize("shape,S", [((1, 1), 1024), ((1, 1), 2048), ((1, 1), 4096),
                                     ((2, 2), 1024)])
def test_dc_params_no_element_gather(topo, shape, S):
    """The D&C secular-parameter program at the N=4096 HEEV shape (nb=256,
    leaf 512): the three merge levels on one chip, and the first on the
    2x2 mesh.  Each root's [RPD, S] pole windows come from whole block
    rows; no gather fetches them one element at a time."""
    from functools import partial

    import dlaf_tpu as dt
    from dlaf_tpu.algorithms import _spmd
    from dlaf_tpu.algorithms.tridiag_dc_dist import _params_kernel
    from dlaf_tpu.matrix.matrix import DistributedMatrix

    n_pad, nb = 4096, 256
    B, P_ = n_pad // S, shape[0] * shape[1]
    RPD = -(-n_pad // P_)
    devs = np.array(topo.devices[:P_]).reshape(shape)
    grid = dt.Grid(Mesh(devs, ("r", "c")))
    dist = dt.Distribution(dt.Size2D(n_pad, n_pad), dt.Size2D(nb, nb), grid.grid_size,
                           dt.Index2D(0, 0))
    rep, stacked = P(), P("r", "c")
    kern = partial(_params_kernel, g=_spmd.Geometry.of(dist), S=S, B=B, n_pad=n_pad,
                   RPD=RPD, iters=42, dt=jnp.dtype(jnp.float32))
    f = jax.jit(jax.shard_map(kern, mesh=grid.mesh, in_specs=(stacked, rep, rep),
                              out_specs=(rep,) * 16, check_vma=False))
    with _x64(False):
        text = f.lower(
            _shape(DistributedMatrix.stacked_shape(dist), grid.stacked_sharding()),
            _shape((n_pad,), NamedSharding(grid.mesh, rep)),
            _shape((B,), NamedSharding(grid.mesh, rep))).compile().as_text()
    gathers = re.findall(r" = \w+\[([0-9,]*)\]\S* gather\(.*slice_sizes=\{([0-9,]+)\}", text)
    assert len(gathers) == len(re.findall(r" gather\(", text))
    # a pole window is an [RPD, S] output: only whole-row gathers may make it
    windows = [(out, sl) for out, sl in gathers if out.count(",") == 1
               and int(out.split(",")[0]) >= RPD and int(out.split(",")[1]) >= S]
    assert {sl for _, sl in windows} <= {f"1,{S}"}, windows


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.complex64])
def test_bt_band_factors_gather_whole_rows(one_chip, dtype):
    """The WY window program at the N=4096 HEEV shape (b=32 after SBR,
    g=32): a gather there moves whole reflector rows (b values and the
    tau beside them), never single elements, and nothing scatters."""
    from functools import partial

    from dlaf_tpu.algorithms.bt_band_hh import _form_factors, hh_schedule

    n, b, g = 4096, 32, 32
    sched = hh_schedule(n, b, g)
    R = int(sched.rows.max())  # the missing-reflector row
    f = jax.jit(partial(_form_factors, sched=sched, b=b, g=g))
    with _x64(False):
        text = f.lower(_shape((R * b,), one_chip, dtype),
                       _shape((R,), one_chip, dtype)).compile().as_text()
    assert not re.findall(r" scatter\(", text)
    sizes = re.findall(r" gather\(.*slice_sizes=\{([0-9,]+)\}", text)
    assert len(sizes) == len(re.findall(r" gather\(", text))
    assert set(sizes) <= {f"1,{b + 1}"}, sizes


def test_bt_band_factors_built_once(monkeypatch):
    """Two back-transforms of one shape build the group schedule and
    compile the window program once."""
    from dlaf_tpu.algorithms import bt_band_hh
    from dlaf_tpu.common.index import Size2D
    from dlaf_tpu.comm.grid import Grid
    from dlaf_tpu.matrix.matrix import DistributedMatrix
    from dlaf_tpu.plan import core as plan

    n, b, g, dt = 31, 3, 2, np.float32
    plan.evict(plan.plan_key("bt_band_factors", (n, b, g, np.dtype(dt))))
    built = []
    schedule = bt_band_hh.hh_schedule
    monkeypatch.setattr(bt_band_hh, "hh_schedule", lambda *a: built.append(a) or schedule(*a))
    R = sum((n - 3 - s) // b + 1 for s in range(n - 2))
    rng = np.random.default_rng(0)
    grid = Grid.create(Size2D(1, 1), jax.devices()[:1])
    hh = (None, None, np.ones(n, dt), rng.standard_normal((R, b)).astype(dt),
          rng.uniform(1, 2, R).astype(dt), b)
    for _ in range(2):
        mat = DistributedMatrix.from_global(grid, rng.standard_normal((n, 5)).astype(dt), (8, 8))
        bt_band_hh.bt_band_to_tridiagonal_hh_dist(hh, mat, group_size=g)
    assert built == [(n, b, g)]
    _, form = plan.lookup(plan.plan_key("bt_band_factors", (n, b, g, np.dtype(dt))))
    assert form._cache_size() == 1


def test_ring_exchange_compiles_on_2x2(mesh_2x2):
    """The remote-DMA panel ring along 'c' of the 2x2 mesh (16 tiles of
    256x256 f32)."""
    from dlaf_tpu.ops import pallas_panel_exchange as ppe

    def fn(y, h):
        return ppe.dma_ring_exchange(
            y, h, "c", ("r", "c"), False, ppe.collective_id_for("bcast", "c"))

    _compile_on_mesh(mesh_2x2, fn, [((16, 256 * 256), jnp.float32),
                                    ((16, 1), jnp.int32)], 2)


def test_ring_consume_compiles_on_2x2(mesh_2x2):
    """The fused trailing-update consume ring.  It keeps the whole local
    trailing matrix in VMEM, so only a 2x2-tile local matrix of 128x128
    tiles fits; real sizes do not (see ROADMAP)."""
    from dlaf_tpu.ops import pallas_panel_exchange as ppe
    from dlaf_tpu.ops import pallas_trailing_update as ptu

    ltr = slots = 2
    mb = 128

    def fn(x, cp, y, h, z):
        return ptu.dma_ring_consume(
            x, y, h, cp, z, "c", ("r", "c"), False,
            ppe.collective_id_for("consume", "c"))

    _compile_on_mesh(mesh_2x2, fn, [
        ((ltr, slots, mb, mb), jnp.float32), ((ltr, mb, mb), jnp.float32),
        ((slots, mb, mb), jnp.float32), ((slots, 1), jnp.int32),
        ((slots, 1), jnp.int32)], 3)


def test_fused_factor_bcast_compiles_on_2x2(mesh_2x2):
    """potrf + panel solve + ring send of the lookahead panel, one kernel.
    Without x64: under it the fused kernels abort inside the compiler."""
    from dlaf_tpu.ops import pallas_panel_exchange as ppe

    ltr, mb = 4, 128

    def fn(d, xc, below, root):
        return ppe.fused_factor_bcast(d, xc, below, root[0], "c", ("r", "c"))

    _compile_on_mesh(mesh_2x2, fn, [
        ((mb, mb), jnp.float32), ((ltr, mb, mb), jnp.float32),
        ((ltr,), jnp.int32), ((1,), jnp.int32)], 2, x64=False)


def test_fused_step_compiles_on_2x2(mesh_2x2):
    """The whole lookahead Cholesky step as one kernel (VMEM-resident local
    matrix, so a toy 2x2-tile local shape)."""
    from dlaf_tpu.ops import pallas_trailing_update as ptu

    ltr, mb = 2, 128

    def fn(x, taken, have, suppress, cp, below1, params):
        return ptu.fused_step(x, taken, have, suppress, cp, below1, params,
                              ("r", "c"))

    _compile_on_mesh(mesh_2x2, fn, [
        ((ltr, ltr, mb, mb), jnp.float32), ((ltr, mb, mb), jnp.float32),
        ((ltr,), jnp.int32), ((ltr,), jnp.int32), ((ltr, mb, mb), jnp.float32),
        ((ltr,), jnp.int32), ((8,), jnp.int32)], 5, x64=False)


def test_distributed_cholesky_fits_one_chip(topo, monkeypatch):
    """The jitted SPMD Cholesky at N=16384 nb=512 on a 1x1 grid, with the
    Pallas diagonal-tile potrf it takes on TPU, fits one chip's HBM."""
    import dlaf_tpu as dt
    from dlaf_tpu.algorithms import _spmd, cholesky
    from dlaf_tpu.matrix.matrix import DistributedMatrix

    # described devices are not the process backend: steer the TPU branch
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    grid = dt.Grid(Mesh(np.array(topo.devices[:1]).reshape(1, 1), ("r", "c")))
    n, nb = 16384, 512
    dist = dt.Distribution(dt.Size2D(n, n), dt.Size2D(nb, nb), grid.grid_size,
                           dt.Index2D(0, 0))
    x = _shape(DistributedMatrix.stacked_shape(dist), grid.stacked_sharding())
    fn = cholesky._compiled(grid, _spmd.Geometry.of(dist), "L", "bucketed")
    with _x64(False):  # x64 triples the compile time
        compiled = fn.lower(x).compile()
    assert "tpu_custom_call" in compiled.as_text()  # the Pallas diagonal potrf
    ma = compiled.memory_analysis()
    used = (ma.argument_size_in_bytes + ma.output_size_in_bytes
            - ma.alias_size_in_bytes + ma.temp_size_in_bytes)
    assert used < HBM_BYTES, ma
