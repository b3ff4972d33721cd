"""Compile the main path for a described TPU v5e, with no chip attached.

The TPU compiler is installed with jax, and it compiles for a topology
that is described rather than attached: it refuses what interpret mode
accepts (BlockSpec tiles off the (8, 128) tiling, VMEM overruns,
programs that do not fit HBM).  So every kernel form the main path
launches is compiled here with ``interpret=False`` at the sizes the chip
runs it, and the tile pickers are checked to emit only tiles the chip
compiles.  Nothing runs: these tests say nothing about results or speed.

The topology is described inside a fixture, never at import: only one
process at a time may load the TPU library, and every test worker
imports this file.
"""
import os

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import NamedSharding, PartitionSpec as P, SingleDeviceSharding

from repro.core import distributed, rulespec
from repro.kernels.fhp_step import ops

TOPOLOGY = "v5e:2x2"
V5E_HBM_BYTES = 16 * 2 ** 30


@pytest.fixture(scope="module")
def topo():
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache
    try:
        desc = topologies.get_topology_desc(platform="tpu",
                                            topology_name=TOPOLOGY)
    except Exception as e:
        pytest.skip(f"no {TOPOLOGY} topology can be described here: {e}")
    # A TPU executable written to the persistent cache cannot be read
    # back without a chip (every later compile would warn), so the cache
    # stays off while this module compiles.
    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield desc
    jax.config.update("jax_enable_compilation_cache", was)
    compilation_cache.reset_cache()


@pytest.fixture(scope="module")
def one_chip(topo):
    return SingleDeviceSharding(topo.devices[0])


@pytest.fixture
def on_tpu(monkeypatch):
    """Code that asks ``jax.default_backend()`` must take its TPU branch
    (no interpret mode) while it is traced for the described chip.
    Traces cached under the CPU answer are dropped on both sides."""
    jax.clear_caches()
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    yield
    jax.clear_caches()


def _spec(shape, sharding, dtype=jnp.uint32):
    return jax.ShapeDtypeStruct(shape, dtype, sharding=sharding)


def _compile(fn, *args):
    """Compile ``fn`` for the described chip; require a Mosaic kernel in
    it and that it fits one chip's HBM."""
    compiled = jax.jit(fn).lower(*args).compile()
    assert "tpu_custom_call" in compiled.as_text()
    mem = compiled.memory_analysis()
    used = (mem.argument_size_in_bytes + mem.output_size_in_bytes
            + mem.temp_size_in_bytes - mem.alias_size_in_bytes)
    assert used < V5E_HBM_BYTES, used
    return compiled


def _legal(bh, bw, h, wd):
    return ops.tile_error(bh, bw, h, wd) is None


# ---------------------------------------------------------------------------
# Kernel forms at real widths
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("variant,p_force", [("fhp2", 0.05), ("bml", 0.0)])
def test_periodic_kernel_autotuned_tile(one_chip, variant, p_force):
    """One launch of the periodic kernel on a 16384 x 16384-site lattice
    with the autotuner's tile: FHP-II with forcing, and BML."""
    h, wd = 16384, 512
    n = rulespec.get_rule(variant).n_planes
    bh, bw, T = ops.autotune_launch(h, wd, n_planes=n)
    assert _legal(bh, bw, h, wd)
    _compile(lambda p, t: ops.fhp_step_pallas(
        p, t, p_force=p_force, variant=variant, block_rows=bh,
        block_words=bw, steps_per_launch=T, interpret=False),
        _spec((n, h, wd), one_chip), _spec((), one_chip, jnp.int32))


def test_static_solid_kernel(one_chip):
    """The 7 dynamic planes plus the read-only solid operand."""
    h, wd, T = 16384, 512, 4
    bh = ops.pick_block_rows(h, wd, steps=T)
    _compile(lambda p, s, t: ops.fhp_step_pallas(
        p, t, p_force=0.05, solid=s, block_rows=bh, steps_per_launch=T,
        interpret=False),
        _spec((7, h, wd), one_chip), _spec((h, wd), one_chip),
        _spec((), one_chip, jnp.int32))


def test_fused_moments_batched(one_chip):
    """B=4 ensemble lanes with in-kernel moments, as the serve engine
    launches them (4096 x 4096 sites per lane)."""
    h, wd, T = 4096, 128, 4
    bh = ops.pick_block_rows(h, wd, steps=T)
    _compile(lambda p, t: ops.fhp_step_pallas(
        p, t, p_force=0.05, block_rows=bh, steps_per_launch=T,
        record_steps=(1, 3), interpret=False),
        _spec((4, 8, h, wd), one_chip), _spec((), one_chip, jnp.int32))


# ---------------------------------------------------------------------------
# The tiles the pickers return are tiles the chip compiles
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("h,wd,variant", [
    (4096, 128, "fhp2"),      # the serve engine's lane
    (12, 4, "fhp2"),          # no power-of-two band divides H: full extent
    (16384, 512, "bml"),
])
def test_autotuned_periodic_tile_compiles(one_chip, h, wd, variant):
    n = rulespec.get_rule(variant).n_planes
    bh, bw, T = ops.autotune_launch(h, wd, n_planes=n)
    assert _legal(bh, bw, h, wd), (bh, bw)
    assert _legal(ops.pick_block_rows(h, wd, n_planes=n), wd, h, wd)
    p_force = 0.05 if variant == "fhp2" else 0.0
    _compile(lambda p, t: ops.run_pallas(
        p, 2 * T, p_force=p_force, t0=t, variant=variant, block_rows=bh,
        block_words=bw, steps_per_launch=T, interpret=False),
        _spec((n, h, wd), one_chip), _spec((), one_chip, jnp.int32))


@pytest.mark.parametrize("hl,wdl", [(16384, 256), (8192, 2048), (8, 32)])
def test_autotuned_sharded_tile_compiles(one_chip, hl, wdl):
    """The joint sharded pick, compiled as the extended launch one shard
    runs (``run_extended`` pads the shard to the tile)."""
    bh, bw, T, d, _ov = ops.autotune_launch(hl, wdl, max_depth=16,
                                            exchange_latency_s=3e-6)
    we = wdl + 2
    assert bh % ops.SUBLANES == 0 and _legal(bh, bw, bh, we), (bh, bw)
    bh_p, bw_p = ops.pick_tile_extended(we, steps=T)
    assert bh_p % ops.SUBLANES == 0 and _legal(bh_p, bw_p, bh_p, we)
    _compile(lambda e, t: ops.run_extended(
        e, d, t0=t, p_force=0.05, y0=-d, xw0=-1, hg=2 * hl, wdg=2 * wdl,
        steps_per_launch=T, block_rows=bh, block_words=bw,
        interpret=False),
        _spec((8, hl + 2 * d, we), one_chip),
        _spec((), one_chip, jnp.int32))


@pytest.mark.parametrize("bh,bw", [(12, 512), (32, 8)])
def test_illegal_explicit_tile_refused(bh, bw):
    """An explicit tile off the (8, 128) tiling is refused by name on the
    compiled path, before Mosaic sees it."""
    with pytest.raises(ValueError, match="multiples of"):
        ops.fhp_step_pallas.lower(
            jax.ShapeDtypeStruct((8, 96, 512), jnp.uint32), 0,
            block_rows=bh, block_words=bw, interpret=False)


# ---------------------------------------------------------------------------
# The sharded stepper on a described 2x2 mesh
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("h,wd,lanes,overlap,moments", [
    (32768, 512, 1, False, False),    # the four-chip lattice
    (32768, 512, 1, True, False),
    (4096, 128, 4, False, True),      # the serve engine's lane group
])
def test_sharded_ensemble_run(topo, on_tpu, h, wd, lanes, overlap, moments):
    """``make_ensemble_run`` on the four chips at depth 4: the 32768 x
    16384-site lattice (each shard 16384 x 256 words), with and without
    the interior/boundary overlap split, and a 4-lane group of
    4096 x 4096 lattices with the psum'd in-kernel moments the serve
    engine audits from."""
    mesh = distributed.make_mesh((2, 2), ("data", "model"),
                                 devices=topo.devices)
    depth = 4
    run, sharding = distributed.make_ensemble_run(
        mesh, depth, variant="fhp2", p_force=0.05, depth=depth,
        use_pallas=True, steps_per_launch=depth, overlap=overlap,
        moments_every=depth if moments else 0)
    compiled = _compile(run, _spec((lanes, 8, h, wd), sharding),
                        _spec((), NamedSharding(mesh, P()), jnp.int32))
    text = compiled.as_text()
    assert "collective-permute" in text
    assert ("all-reduce" in text) == moments
