"""Geometry subsystem: shard-local rasterization must equal the global
rasterization sliced to the shard's window -- for every primitive,
every mesh shape, every origin -- because every predicate is an
integer-exact function of global node coordinates.  Plus packing and
composition invariants.
"""
import numpy as np
import pytest

from hypothesis import given, settings, strategies as st

from repro.core import bitplane, rules
from repro.geometry import (Disk, Empty, HalfPlane, ObstacleArray,
                            PorousMedium, Rectangle, channel_walls,
                            pack_mask, rasterize, solid_words)

H, W = 48, 192   # global lattice for the property tests (W % 32 == 0)


def _geometries():
    """One representative of every primitive plus compositions."""
    return {
        "disk": Disk(H // 2, W // 4, 9),
        "walls": channel_walls(H),
        "rect": Rectangle(0, H // 2, 0, W // 4),
        "halfplane": HalfPlane("x", W - 3, above=True),
        "array": ObstacleArray(H // 2, W // 8, 4, 16, 32),
        "porous": PorousMedium(1, H - 1, W // 3, W // 2, 0.15, seed=7),
        "union": channel_walls(H) | Disk(H // 2, W // 4, 9),
        "intersect": (ObstacleArray(H // 2, W // 8, 4, 16, 32)
                      & Rectangle(8, H - 8, 0, W)),
        "empty": Empty(),
    }


# ---------------------------------------------------------------------------
# Property: shard windows reproduce the global rasterization, any mesh.
# ---------------------------------------------------------------------------

@settings(max_examples=60)
@given(st.integers(1, 4),      # ny: shards in y
       st.integers(1, 4),      # nx: shards in x (over words)
       st.integers(0, 3),      # iy
       st.integers(0, 3),      # ix
       st.integers(0, 8))      # which geometry
def test_shard_raster_equals_global_slice(ny, nx, iy, ix, gi):
    """A shard rasterizing its own window in global coordinates gets the
    slice of the global mask -- the invariant that lets each shard build
    its solid tile without a host gather."""
    iy, ix = iy % ny, ix % nx
    name, geom = sorted(_geometries().items())[gi]
    hl, wl = H // ny, W // nx  # W splits at word granularity below
    full = rasterize(geom, (H, W))
    tile = rasterize(geom, (hl, wl), origin=(iy * hl, ix * wl))
    want = full[iy * hl:(iy + 1) * hl, ix * wl:(ix + 1) * wl]
    assert (tile == want).all(), (name, ny, nx, iy, ix)


@settings(max_examples=30)
@given(st.integers(1, 3), st.integers(1, 3), st.integers(0, 2),
       st.integers(0, 2), st.integers(0, 8))
def test_shard_solid_words_equal_global_slice(ny, nx, iy, ix, gi):
    """Same property on the packed word layout (word-granular x origin),
    exactly the tile the sharded stepper consumes."""
    iy, ix = iy % ny, ix % nx
    name, geom = sorted(_geometries().items())[gi]
    wd = W // 32
    assert wd % nx == 0
    hl, wdl = H // ny, wd // nx
    full = solid_words(geom, (H, wd))
    tile = solid_words(geom, (hl, wdl), origin_words=(iy * hl, ix * wdl))
    want = full[iy * hl:(iy + 1) * hl, ix * wdl:(ix + 1) * wdl]
    assert (tile == want).all(), (name, ny, nx, iy, ix)


# ---------------------------------------------------------------------------
# Packing and primitive invariants.
# ---------------------------------------------------------------------------

def test_pack_mask_matches_bitplane_layout():
    """solid_words must produce exactly the plane-7 words that
    bitplane.pack derives from a byte state with the same solid mask."""
    import jax.numpy as jnp
    mask = rasterize(channel_walls(H) | Disk(H // 2, W // 4, 9), (H, W))
    state = np.where(mask, np.uint8(rules.SOLID_MASK), np.uint8(0))
    planes = bitplane.pack(jnp.asarray(state))
    assert (np.asarray(planes[7]) == pack_mask(mask)).all()
    assert (np.asarray(planes[:7]) == 0).all()


def test_disk_triangular_metric():
    """The disk is round in the physical metric: odd rows sit half a
    lattice constant east, so the mask is parity-aware (row y and row
    y+1 of a big disk differ in their western extent) and symmetric
    about the centre row."""
    d = Disk(24, 24, 8)
    m = rasterize(d, (48, 48))
    assert m[24, 24] and m.sum() > 0
    # vertical symmetry about the centre row (24 +- k rows match: equal
    # parity rows have identical x offsets)
    for k in (1, 2, 3):
        assert (m[24 + k] == m[24 - k]).all()
    # radius bound: nothing beyond r rows of the centre vertically
    # (3*dy^2 > 4r^2 for dy > 2r/sqrt(3) ~ 1.155r)
    assert not m[:24 - 10].any() and not m[24 + 11:].any()


def test_obstacle_array_periodicity():
    arr = ObstacleArray(8, 8, 3, 16, 16)
    m = rasterize(arr, (64, 64))
    # the pattern repeats with the pitch in y
    assert (m[:16] == m[16:32]).all()
    assert (m[:, :16] == m[:, 16:32]).all()


def test_porous_medium_seeded_and_bounded():
    p1 = rasterize(PorousMedium(4, 44, 32, 96, 0.2, seed=1), (H, W))
    p1b = rasterize(PorousMedium(4, 44, 32, 96, 0.2, seed=1), (H, W))
    p2 = rasterize(PorousMedium(4, 44, 32, 96, 0.2, seed=2), (H, W))
    assert (p1 == p1b).all(), "same seed must reproduce the medium"
    assert (p1 != p2).any(), "different seeds must differ"
    assert not p1[:4].any() and not p1[44:].any(), "bounded in y"
    assert not p1[:, :32].any() and not p1[:, 96:].any(), "bounded in x"
    frac = p1[4:44, 32:96].mean()
    assert 0.1 < frac < 0.3, frac


def test_union_intersection_algebra():
    a, b = Rectangle(0, 10, 0, 10), Rectangle(5, 20, 5, 20)
    u = rasterize(a | b, (24, 32))
    i = rasterize(a & b, (24, 32))
    ma, mb = rasterize(a, (24, 32)), rasterize(b, (24, 32))
    assert (u == (ma | mb)).all()
    assert (i == (ma & mb)).all()
    assert not rasterize(Empty(), (24, 32)).any()


def test_jnp_window_matches_numpy():
    """Primitives evaluate identically on jnp coordinate windows (the
    device-side rasterization path)."""
    import jax.numpy as jnp
    geom = channel_walls(H) | Disk(H // 2, W // 4, 9) | \
        PorousMedium(1, H - 1, W // 3, W // 2, 0.15, seed=7)
    yy = jnp.arange(H, dtype=jnp.int32)[:, None]
    xx = jnp.arange(W, dtype=jnp.int32)[None, :]
    got = np.asarray(jnp.broadcast_to(geom.mask(yy, xx), (H, W)))
    assert (got == rasterize(geom, (H, W))).all()
