"""Temporal blocking + ensemble lanes vs the bit-plane reference.

``run_pallas(steps_per_launch=T)`` must be bit-identical to T applications
of ``bitplane.step_planes`` (the oracle behind ``ref.py``) for every
``(T, p_force, y0/xw0)``, including non-multiple step counts (the
single-step remainder path) and batched ensemble stacks.
"""
import jax.numpy as jnp
import pytest

from repro.core import bitplane, byte_step
from repro.kernels.fhp_step.ops import (autotune_launch, fhp_step_pallas,
                                        launch_cost, pick_block_rows,
                                        run_pallas, vmem_bytes,
                                        VMEM_BUDGET_BYTES)


def state(h, w, seed=0):
    return bitplane.pack(jnp.asarray(
        byte_step.make_channel(h, w, density=0.3, seed=seed)))


def ref_steps(p, n, t0=0, p_force=0.0, y0=0, xw0=0):
    for s in range(n):
        p = bitplane.step_planes(p, t0 + s, p_force=p_force, y0=y0, xw0=xw0)
    return p


@pytest.mark.parametrize("T", [1, 2, 4])
@pytest.mark.parametrize("p_force", [0.0, 0.1])
def test_temporal_block_equivalence(T, p_force):
    p = state(16, 64, seed=T)
    out_k = run_pallas(p, T, t0=3, p_force=p_force, steps_per_launch=T,
                       block_rows=8)
    out_r = ref_steps(p, T, t0=3, p_force=p_force)
    assert bool((out_k == out_r).all()), (T, p_force)


@pytest.mark.parametrize("T,y0,xw0", [(2, 16, 2), (2, 33, 7), (4, 33, 7)])
def test_temporal_block_sharded_offsets(T, y0, xw0):
    """Odd y0 exercises the parity offset; any offset shifts the RNG."""
    p = state(16, 64, seed=5)
    out_k = run_pallas(p, T, t0=1, p_force=0.1, y0=y0, xw0=xw0,
                       steps_per_launch=T, block_rows=4)
    out_r = ref_steps(p, T, t0=1, p_force=0.1, y0=y0, xw0=xw0)
    assert bool((out_k == out_r).all()), (T, y0, xw0)


@pytest.mark.parametrize("steps,T", [(5, 2), (7, 4), (3, 4),
                                     (4, 4), (8, 4), (12, 4), (8, 2),
                                     (13, 4), (9, 2)])
def test_temporal_remainder_steps(steps, T):
    """1 to 4 full launches, with and without a ``steps % T`` remainder
    launch: the first launch reads the input, the later ones alternate
    two buffers, and the trailing steps run as one shorter launch."""
    p = state(16, 64, seed=7)
    out_k = run_pallas(p, steps, p_force=0.02, steps_per_launch=T,
                       block_rows=8)
    out_r = ref_steps(p, steps, p_force=0.02)
    assert bool((out_k == out_r).all()), (steps, T)


def test_temporal_wrap_band_count_one():
    """T == block_rows with a single grid band: halos are the band itself,
    and every apron row sits past the periodic wrap."""
    p = state(4, 64, seed=9)
    out_k = run_pallas(p, 4, p_force=0.05, steps_per_launch=4, block_rows=4)
    out_r = ref_steps(p, 4, p_force=0.05)
    assert bool((out_k == out_r).all())


@pytest.mark.parametrize("T", [1, 2])
def test_batched_lanes_match_unbatched(T):
    """Every ensemble lane is bit-identical to its own unbatched run."""
    lanes = [state(16, 64, seed=s) for s in range(3)]
    pb = jnp.stack(lanes)
    out_b = run_pallas(pb, 2 * T, p_force=0.1, steps_per_launch=T,
                       block_rows=8)
    assert out_b.shape == pb.shape
    for i, lane in enumerate(lanes):
        out_r = ref_steps(lane, 2 * T, p_force=0.1)
        assert bool((out_b[i] == out_r).all()), i


def test_batched_single_step_kernel():
    pb = jnp.stack([state(8, 32, seed=1), state(8, 32, seed=2)])
    out = fhp_step_pallas(pb, 4, p_force=0.3)
    for i in range(2):
        assert bool((out[i] == bitplane.step_planes(pb[i], 4, p_force=0.3)).all())


def test_temporal_mass_conserved():
    p = state(32, 128, seed=11)
    m0 = int(bitplane.density_total(p))
    p2 = run_pallas(p, 8, p_force=0.1, steps_per_launch=4)
    assert int(bitplane.density_total(p2)) == m0


def test_autotune_launch_valid():
    for h, wd in [(1024, 128), (4096, 512), (64, 32), (8192, 2048)]:
        bh, bw, T = autotune_launch(h, wd)
        assert h % bh == 0 and wd % bw == 0 and 1 <= T <= bh
        assert bw == wd or T <= bw          # x apron must fit the tile
        assert vmem_bytes(bh, wd, T, bw) <= VMEM_BUDGET_BYTES
        # temporal blocking must never be picked at a modeled-cost loss
        # over the single-step default config
        assert (launch_cost(bh, T, bw, wd)
                <= launch_cost(pick_block_rows(h, wd), 1))


def test_pick_block_rows_respects_halo_depth():
    bh = pick_block_rows(64, 32, steps=8)
    assert bh >= 8
    with pytest.raises(ValueError):
        pick_block_rows(64, 10 ** 7, steps=8)  # nothing fits


def test_rng_planes_require_single_step():
    p = state(16, 64)
    with pytest.raises(ValueError):
        fhp_step_pallas(p, 0, rng_in_kernel=False, steps_per_launch=2,
                        block_rows=8)
