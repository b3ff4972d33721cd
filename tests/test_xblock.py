"""2-D (x x y) blocking bit-exactness + the joint-tile autotuner.

The x-blocked kernel grid must be bit-identical to the bit-plane oracle
(``bitplane.step_planes``, the reference behind ``ref.py``) for every
``(Wd, block_words, T)`` -- odd and non-power-of-two word counts,
single-word and prime tiles -- across all four kernel variants:

* periodic mode (wrapping x index maps; the tile rotate's edge garbage
  must be consumed by the one-word-per-side-per-step shrink);
* extended-shard mode (clamped x maps + word padding to a block
  multiple: pad garbage must stay within the dropped halo word);
* batched ensemble lanes;
* static-solid mode (nine overlapping views of the read-only solid).

Plus the VMEM story the 2-D tile exists for: ``autotune_launch`` must
admit ``T >= 7`` at ``wdl = 2048`` (the old full-row kernel was
VMEM-bound there) and the static-solid operand must be priced in
``vmem_bytes``.
"""
import jax.numpy as jnp
import pytest

from hypothesis import given, settings, strategies as st

from repro.core import bitplane, byte_step
from repro.kernels.fhp_step.ops import (autotune_launch, fhp_step_pallas,
                                        pick_tile_extended, run_extended,
                                        run_pallas, vmem_bytes,
                                        VMEM_BUDGET_BYTES)


def state(h, w, seed=0):
    return bitplane.pack(jnp.asarray(
        byte_step.make_channel(h, w, density=0.3, seed=seed)))


def ref_steps(p, n, t0=0, p_force=0.0):
    for s in range(n):
        p = bitplane.step_planes(p, t0 + s, p_force=p_force)
    return p


def periodic_ext(p, d):
    """Manually halo-extend a periodic lattice by d rows / 1 word."""
    ext = jnp.concatenate([p[..., -1:], p, p[..., :1]], axis=-1)
    return jnp.concatenate([ext[..., -d:, :], ext, ext[..., :d, :]],
                           axis=-2)


# ---------------------------------------------------------------------------
# Periodic mode: wrapping 3x3 views, including the run_pallas remainder.
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("wd,bw,T", [
    (6, 1, 1),    # single-word tiles, non-power-of-two width
    (6, 2, 2),    # even tile
    (6, 3, 2),    # prime tile
    (5, 1, 1),    # odd width, single-word tiles
    (10, 5, 4),   # odd tile count, deep T
    (8, 4, 4),    # T == bw: apron is the whole neighbour tile
])
def test_periodic_xblock_matches_reference(wd, bw, T):
    h = 16
    p = state(h, 32 * wd, seed=wd + bw)
    steps = 2 * T + 1            # exercises the remainder launch too
    out = run_pallas(p, steps, t0=3, p_force=0.1, steps_per_launch=T,
                     block_rows=4, block_words=bw)
    want = ref_steps(p, steps, t0=3, p_force=0.1)
    assert bool((out == want).all()), (wd, bw, T)


def test_periodic_xblock_batched_lanes():
    lanes = [state(16, 192, seed=s) for s in range(3)]
    pb = jnp.stack(lanes)
    out = run_pallas(pb, 4, p_force=0.1, steps_per_launch=2,
                     block_rows=8, block_words=2)
    for i, lane in enumerate(lanes):
        assert bool((out[i] == ref_steps(lane, 4, p_force=0.1)).all()), i


def test_periodic_xblock_precomputed_rng_planes():
    """T=1 with host-side chirality/force planes through the 2-D grid."""
    p = state(8, 192, seed=2)
    out = fhp_step_pallas(p, 5, p_force=0.2, rng_in_kernel=False,
                          block_rows=4, block_words=3)
    want = bitplane.step_planes(p, 5, p_force=0.2)
    assert bool((out == want).all())


def test_xblock_rejects_bad_tiles():
    p = state(16, 192)           # Wd = 6
    with pytest.raises(ValueError):
        fhp_step_pallas(p, 0, block_rows=8, block_words=4)  # 4 !| 6
    with pytest.raises(ValueError):
        run_pallas(p, 4, steps_per_launch=4, block_rows=8,
                   block_words=2)                            # T > bw


# ---------------------------------------------------------------------------
# Extended-shard mode: clamped views + word padding to a block multiple.
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("wd,bw,T,d", [
    (5, 2, 2, 4),   # Wde = 7 pads to 8: pad-word garbage must stay out
    (7, 3, 2, 3),   # prime tile + remainder launch
    (6, 2, 1, 2),   # T=1, several launches
    (4, 4, 4, 4),   # bw < Wde = 6 but T == bw
])
def test_extended_xblock_matches_reference(wd, bw, T, d):
    h = 16
    p = state(h, 32 * wd, seed=wd + d)
    ext = periodic_ext(p, d)
    out = run_extended(ext, d, t0=5, p_force=0.1, y0=-d, xw0=-1,
                       hg=h, wdg=wd, steps_per_launch=T, block_rows=8,
                       block_words=bw)
    got = out[..., d:d + h, 1:1 + wd]
    want = ref_steps(p, d, t0=5, p_force=0.1)
    assert bool((got == want).all()), (wd, bw, T, d)


@settings(max_examples=10, deadline=None)
@given(st.integers(0, 10 ** 6))
def test_extended_xblock_property(point):
    """Any (Wd, bw, T <= min(d, bw), d) point is bit-exact: the global-mod
    RNG makes redundant x-apron compute draw the owning word's stream.
    The point is decoded from one wide sampled integer; each point
    compiles a fresh interpret-mode kernel, hence no deadline."""
    wd = 3 + point % 6                # 3..8: odd + non-pow2 widths
    bw = 1 + (point // 6) % 3         # 1..3: single-word + prime tiles
    T = 1 + (point // 18) % 2         # 1..2
    d = 1 + (point // 36) % 4         # 1..4
    T = min(T, d, bw)
    h = 8
    p = state(h, 32 * wd, seed=wd * 8 + bw)
    ext = periodic_ext(p, d)
    out = run_extended(ext, d, t0=2, p_force=0.05, y0=-d, xw0=-1,
                       hg=h, wdg=wd, steps_per_launch=T, block_rows=4,
                       block_words=bw)
    got = out[..., d:d + h, 1:1 + wd]
    want = ref_steps(p, d, t0=2, p_force=0.05)
    assert bool((got == want).all()), (wd, bw, T, d)


def test_extended_xblock_batched_lanes():
    d, T, h, wd = 2, 2, 16, 5
    lanes = [state(h, 32 * wd, seed=s) for s in range(2)]
    pb = jnp.stack(lanes)
    ext = periodic_ext(pb, d)
    out = run_extended(ext, d, t0=1, p_force=0.05, y0=-d, xw0=-1,
                       hg=h, wdg=wd, steps_per_launch=T, block_rows=8,
                       block_words=2)
    got = out[..., d:d + h, 1:1 + wd]
    for i, lane in enumerate(lanes):
        assert bool((got[i] == ref_steps(lane, d, t0=1, p_force=0.05)).all())


def test_extended_xblock_static_solid():
    """The nine solid views + word padding of the static-geometry cache:
    7-plane x-blocked launches == the 8-plane periodic reference."""
    from repro import scenarios
    d, T = 3, 2
    sc = scenarios.get("backward_step", height=16, width=160)
    h, wd = sc.height, sc.width // 32
    p = sc.initial_planes()
    ext = periodic_ext(p, d)
    out = run_extended(ext[:7], d, t0=5, p_force=0.1, y0=-d, xw0=-1,
                       hg=h, wdg=wd, steps_per_launch=T, block_rows=8,
                       block_words=2, solid_ext=ext[7])
    got = out[..., d:d + h, 1:1 + wd]
    want = ref_steps(p, d, t0=5, p_force=0.1)
    assert bool((got == want[:7]).all())


# ---------------------------------------------------------------------------
# The VMEM story: the 2-D tile lifts the wide-shard ceiling.
# ---------------------------------------------------------------------------

def test_2d_tile_admits_deep_T_on_wide_shards():
    """At wdl=2048 the full-row band is VMEM-bound at T=7 (T=8 does not
    fit any block_rows); an x-blocked tile admits T=8 with room to
    spare, and the sharded autotuner now picks a 2-D point there."""
    we = 2048 + 2
    # old 1-D model: no block_rows fits T=8
    assert all(vmem_bytes(bh, we, 8) > VMEM_BUDGET_BYTES
               for bh in (8, 16, 32))
    # 2-D tiles fit T=8 (and T=7) comfortably
    assert vmem_bytes(32, we, 8, 256) <= VMEM_BUDGET_BYTES
    assert vmem_bytes(32, we, 7, 256) <= VMEM_BUDGET_BYTES
    bh, bw, T, d, _ov = autotune_launch(8192, 2048, max_depth=16)
    assert T >= 7, (bh, bw, T, d)
    assert bw < we, "the tuner must split x on a VMEM-bound wide shard"
    assert vmem_bytes(bh, we, T, bw) <= VMEM_BUDGET_BYTES
    # the picker helper agrees a 2-D tile is required for deep T there
    bh_p, bw_p = pick_tile_extended(we, steps=8)
    assert bw_p < we
    assert vmem_bytes(bh_p, we, 8, bw_p) <= VMEM_BUDGET_BYTES


def test_vmem_accounts_static_solid_operand():
    """The read-only pre-extended solid operand must be priced: the
    static path holds its own views on top of the 7 dynamic planes, so
    a tile that barely fits dynamically can overflow statically."""
    dyn = vmem_bytes(16, 512, 4, 64)
    sta = vmem_bytes(16, 512, 4, 64, static_solid=True)
    assert sta > dyn * 7 / 8          # not just the 7/8 plane cut
    # 1-D static accounting too (3 views + assembled band)
    assert (vmem_bytes(8, 512, 2, static_solid=True)
            > vmem_bytes(8, 512, 2) * 7 / 8)
    # and the sharded tuner respects the budget on the static path
    bh, bw, T, d, _ov = autotune_launch(8192, 2048, max_depth=16,
                                        static_solid=True)
    assert vmem_bytes(bh, 2050, T, bw,
                      static_solid=True) <= VMEM_BUDGET_BYTES


def test_sharded_traffic_model_prices_x_apron():
    """2-D blocking must never look free: at equal (bh, T, depth) the
    x-blocked tile reads strictly more HBM (the T-word apron per side),
    and the 1-D point is recovered exactly at bw >= width."""
    from repro.roofline.analysis import sharded_fhp_traffic
    base = sharded_fhp_traffic(1024, 128, depth=8, T=4, block_rows=16)
    full = sharded_fhp_traffic(1024, 128, depth=8, T=4, block_rows=16,
                               block_words=130)
    assert base["hbm_bytes_per_site_step"] == full["hbm_bytes_per_site_step"]
    blk = sharded_fhp_traffic(1024, 128, depth=8, T=4, block_rows=16,
                              block_words=32)
    assert (blk["hbm_bytes_per_site_step"]
            > base["hbm_bytes_per_site_step"])
    assert blk["x_blocks"] == pytest.approx((128 + 2 + 31) // 32)
    # ICI terms do not depend on the tile shape
    assert blk["ici_bytes_per_site_step"] == base["ici_bytes_per_site_step"]
    assert blk["exchanges_per_step"] == base["exchanges_per_step"]
