"""Plain reference steppers for the benchmark's correctness check.

Written from the rule definitions alone, in straightforward ``jax.numpy``
over packed bit planes, and importing nothing of the system under test:

* FHP-II (Frisch-Hasslacher-Pomeau with rest particles) on the
  triangular lattice mapped to a rectangle, odd rows shifted east by half
  a lattice constant; bounce-back on solid sites; the body force turns a
  west mover into an east mover where a Bernoulli(p) coin says so;
* BML traffic: east cars move on even steps, north cars on odd steps, a
  car advances iff the cell ahead was empty before the sub-step.

Layout (shared with every consumer of the planes): a state is a
``(n_planes, H, W // 32)`` uint32 stack; bit ``b`` of word ``w`` in row
``y`` is node ``(y, 32 * w + b)``.  FHP planes 0-5 are the movers along
E, NE, NW, W, SW, SE, plane 6 the rest particle, plane 7 the solid flag.
BML plane 0 holds east cars, plane 1 north cars.

The random bits are the rule's own counter-based stream: a murmur3
finaliser of ``(row * 0x01000193 + word) ^ (t * golden + salt)``, one
word of 32 coins per (row, word) for chirality (salt 0x11), and a
16-bit MSB-first comparator of such words for the forcing coin (salt
0x22).  A stochastic rule is bit-exact only against the same stream.

``run_band`` advances a band of rows of a periodic lattice by ``steps``
steps from an apron of ``steps`` rows on each side, so a large lattice
is checked band by band in bounded memory.  ``seeded_fill`` makes a
benchmark's initial planes from its seed with the same hash and
comparator.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np

WORD = 32
N_DIR = 6
REST, SOLID = 6, 7
CX2 = (2, 1, -1, -2, -1, 1)          # doubled x momentum per direction
CY = (0, 1, 1, 0, -1, -1)            # y momentum (units of sqrt(3)/2)
# (dx for an even source row, dx for an odd source row, dy) per direction.
MOVES = ((1, 1, 0), (0, 1, 1), (-1, 0, 1), (-1, -1, 0), (-1, 0, -1),
         (0, 1, -1))

_M1, _M2, _GOLD, _FNV = 0x85EBCA6B, 0xC2B2AE35, 0x9E3779B9, 0x01000193
CHIRALITY_SALT, FORCE_SALT = 0x11, 0x22
FORCE_BITS = 16


def _u32(x):
    return jnp.asarray(x, jnp.uint32)


def _mix(x):
    x = x ^ (x >> 16)
    x = x * _u32(_M1)
    x = x ^ (x >> 13)
    x = x * _u32(_M2)
    return x ^ (x >> 16)


def random_words(rows, cols, t, salt: int):
    """32 coins per (row, word): ``rows`` (h, 1), ``cols`` (1, wd) uint32."""
    ctr = rows * _u32(_FNV) + cols
    tt = _u32(t) * _u32(_GOLD) + _u32((salt * _M2) & 0xFFFFFFFF)
    return _mix(ctr ^ tt)


def below(draw, thresholds, shape):
    """For each of ``thresholds`` (integers in [0, 2**16]), the bits at
    which the 16-bit number whose bit ``i`` is ``draw(i)`` (a word of
    random bits), most significant bit first, lies below the threshold:
    the comparator R < P over random bit planes."""
    less = [jnp.zeros(shape, jnp.uint32) for _ in thresholds]
    equal = [jnp.full(shape, 0xFFFFFFFF, jnp.uint32) for _ in thresholds]
    set_bits = [q & -q for q in thresholds if q]
    if not set_bits:
        return less
    lowest = min(set_bits).bit_length() - 1  # later bits change no result
    for i in range(FORCE_BITS - 1, lowest - 1, -1):
        r = draw(i)
        for j, q in enumerate(thresholds):
            if (q >> i) & 1:
                less[j] = less[j] | (equal[j] & ~r)
                equal[j] = equal[j] & r
            else:
                equal[j] = equal[j] & ~r
    return less


def force_words(rows, cols, t, p: float):
    """Bernoulli(round(p * 2**16) / 2**16) per bit, from the forcing
    coin's 16 random words."""
    pq = int(round(min(max(p, 0.0), 1.0) * (1 << FORCE_BITS)))
    shape = jnp.broadcast_shapes(rows.shape, cols.shape)
    return below(lambda i: random_words(rows, cols, t,
                                        FORCE_SALT * 0x100 + i),
                 [pq], shape)[0]


def seed_key(seed: int):
    """A seed of up to 64 bits as two uint32 words: an argument of the
    jitted state makers, so one compiled program serves every seed."""
    return jnp.array([seed & 0xFFFFFFFF, (seed >> 32) & 0xFFFFFFFF],
                     jnp.uint32)


def seeded_words(key, stream: int, rows, cols):
    """Uniform uint32 words per (row, word) from the two-word seed ``key``
    and a stream number: the counter hash above, keyed by the seed."""
    k = _mix(key[0] ^ _mix(key[1] + _u32(stream) * _u32(_GOLD)))
    return _mix(_mix((rows * _u32(_FNV) + cols) ^ k) + k)


def seeded_fill(key, stream: int, rows, cols, shares):
    """One plane per entry of ``shares``: plane ``i`` set where a seeded
    16-bit draw per bit falls in the ``i``-th of consecutive bins of
    widths ``shares``, so the planes are disjoint."""
    shape = jnp.broadcast_shapes(rows.shape, cols.shape)
    edges = [int(round(e * (1 << FORCE_BITS))) for e in np.cumsum(shares)]
    less = below(lambda i: seeded_words(key, stream * FORCE_BITS + i,
                                        rows, cols), edges, shape)
    return [less[0]] + [b & ~a for a, b in zip(less, less[1:])]


def shift_x(p, dx: int):
    """Node x of the result holds node x - dx of ``p`` (periodic in x)."""
    if dx == 1:
        return (p << 1) | (jnp.roll(p, 1, axis=-1) >> (WORD - 1))
    if dx == -1:
        return (p >> 1) | (jnp.roll(p, -1, axis=-1) << (WORD - 1))
    return p


def _rotate(dirs, k):
    return frozenset((d + k) % N_DIR for d in dirs)


def fhp2_table():
    """``(movers in, rest in, movers out for chirality 0, for 1, rest
    out)``; rest ``None`` means "any" on input and "unchanged" on output."""
    table = []
    for i in range(3):                                   # head-on pairs
        pair = frozenset({i, i + 3})
        table.append((pair, None, _rotate(pair, 1), _rotate(pair, -1), None))
    for i in range(2):                                   # symmetric triples
        tri = frozenset({i, i + 2, i + 4})
        table.append((tri, None, _rotate(tri, 1), _rotate(tri, 1), None))
    for i in range(3):                                   # two head-on pairs
        quad = frozenset({i, i + 1, i + 3, (i + 4) % N_DIR})
        table.append((quad, None, _rotate(quad, 1), _rotate(quad, -1), None))
    for i in range(N_DIR):                               # rest exchange
        one, two = frozenset({i}), frozenset({(i - 1) % N_DIR,
                                              (i + 1) % N_DIR})
        table.append((one, True, two, two, False))
        table.append((two, False, one, one, True))
    return table


def fhp2_collide(a, chi):
    """FHP-II collision of the 8 streamed planes, bounce-back on solids."""
    fired = jnp.zeros_like(a[0])
    moved = [jnp.zeros_like(a[0]) for _ in range(N_DIR)]
    rest_set = jnp.zeros_like(a[0])
    rest_clear = jnp.zeros_like(a[0])
    for movers, rest_in, out0, out1, rest_out in fhp2_table():
        hit = jnp.full_like(a[0], 0xFFFFFFFF)
        for d in range(N_DIR):
            hit = hit & (a[d] if d in movers else ~a[d])
        if rest_in is not None:
            hit = hit & (a[REST] if rest_in else ~a[REST])
        fired = fired | hit
        for d in range(N_DIR):
            if d in out0 and d in out1:
                moved[d] = moved[d] | hit
            elif d in out0:
                moved[d] = moved[d] | (hit & ~chi)
            elif d in out1:
                moved[d] = moved[d] | (hit & chi)
        if rest_out is True:
            rest_set = rest_set | hit
        elif rest_out is False:
            rest_clear = rest_clear | hit
    solid = a[SOLID]
    out = []
    for d in range(N_DIR):
        fluid = (a[d] & ~fired) | moved[d]
        out.append((solid & a[(d + 3) % N_DIR]) | (~solid & fluid))
    rest = (a[REST] & ~rest_clear) | rest_set
    out.append((solid & a[REST]) | (~solid & rest))
    out.append(solid)
    return out


def fhp2_step(planes, t, rows, p_force: float):
    """One FHP-II step of ``(8, h, wd)`` planes whose row ``i`` is global
    row ``rows[i, 0]`` (periodic in both axes within the array)."""
    wd = planes.shape[-1]
    cols = jnp.arange(wd, dtype=jnp.uint32)[None, :]
    even = (rows & 1) == 0
    streamed = []
    for d, (dx_even, dx_odd, dy) in enumerate(MOVES):
        p = jnp.where(even, shift_x(planes[d], dx_even),
                      shift_x(planes[d], dx_odd))
        streamed.append(jnp.roll(p, dy, axis=-2) if dy else p)
    streamed += [planes[REST], planes[SOLID]]
    chi = random_words(rows, cols, t, CHIRALITY_SALT)
    out = fhp2_collide(streamed, chi)
    if p_force:
        push = out[3] & ~out[0] & ~out[SOLID] & force_words(rows, cols, t,
                                                            p_force)
        out[3] = out[3] ^ push
        out[0] = out[0] | push
    return jnp.stack(out)


def bml_step(planes, t, rows, p_force: float = 0.0):
    """One BML sub-step of ``(2, h, wd)`` planes (``rows`` unused: the
    rule has no row parity and no random bits)."""
    del rows, p_force
    east, north = planes[0], planes[1]
    occ = east | north
    ahead_x = shift_x(occ, -1)                  # the cell at x + 1
    ahead_y = jnp.roll(occ, -1, axis=-2)        # the cell at y + 1
    new_east = (east & ahead_x) | (shift_x(east, 1) & ~occ)
    new_north = (north & ahead_y) | (jnp.roll(north, 1, axis=-2) & ~occ)
    is_east = (jnp.asarray(t, jnp.int32) % 2) == 0
    return jnp.stack([jnp.where(is_east, new_east, east),
                      jnp.where(is_east, north, new_north)])


STEPS = {"fhp2": fhp2_step, "bml": bml_step}


def run_rows(planes, t0, rows, steps: int, rule: str, p_force: float):
    """``steps`` steps from global time ``t0`` (traced) of ``planes``
    whose rows are the global rows ``rows`` ((h, 1) uint32)."""
    step = STEPS[rule]
    return jax.lax.fori_loop(
        0, steps, lambda i, s: step(s, t0 + i, rows, p_force), planes)


def band_rows(h: int, r0, rows: int, apron: int):
    """Global rows ``[r0 - apron, r0 + rows + apron)`` of a periodic
    lattice of ``h`` rows, wrapped into ``[0, h)``."""
    return (r0 - apron + jnp.arange(rows + 2 * apron, dtype=jnp.int32)) % h


def run_band(ext, glob, t0, *, steps: int, rule: str, p_force: float):
    """The middle rows of the band ``ext`` (n, rows + 2 * steps, Wd),
    whose rows are the global rows ``glob`` (``band_rows(h, r0, rows,
    steps)``), after ``steps`` steps from time ``t0``: the band is
    advanced as if periodic, and the apron of ``steps`` rows on each
    side, as far as the wrap-around error can reach, is cut away."""
    out = run_rows(ext, t0, glob.astype(jnp.uint32)[:, None], steps, rule,
                   p_force)
    return out[:, steps:out.shape[1] - steps]


def pack(bits: np.ndarray) -> np.ndarray:
    """(..., h, w) bool -> (..., h, w // 32) uint32, node x in bit x % 32."""
    *lead, h, w = bits.shape
    weights = np.uint32(1) << np.arange(WORD, dtype=np.uint32)
    return (bits.reshape(*lead, h, w // WORD, WORD).astype(np.uint32)
            * weights).sum(axis=-1, dtype=np.uint32)


def cylinder_planes(height: int, width: int, density: float,
                    seed: int) -> np.ndarray:
    """The ``cylinder`` job's initial FHP-II planes: each of the 7 fluid
    channels filled with probability ``density`` from
    ``numpy.random.default_rng(seed)``; solid walls on the first and last
    row and a solid disk of radius ``max(2, height // 9)`` around node
    ``(height // 2, width // 4)``, in the triangular metric; solid sites
    hold no particles."""
    rng = np.random.default_rng(seed)
    fluid = rng.random((7, height, width)) < density
    y = np.arange(height, dtype=np.int64)[:, None]
    x = np.arange(width, dtype=np.int64)[None, :]
    cy, cx, r = height // 2, width // 4, max(2, height // 9)
    dy = y - cy
    dx2 = (2 * x + (y & 1)) - (2 * cx + (cy & 1))
    solid = (3 * dy * dy + dx2 * dx2 <= (2 * r) ** 2) | (y < 1) \
        | (y >= height - 1)
    solid = np.broadcast_to(solid, (height, width))
    return pack(np.concatenate([fluid & ~solid, solid[None]], axis=0))


@jax.jit
def _row_counts(planes):
    return jax.lax.population_count(planes).sum(axis=-1, dtype=jnp.int32)


def plane_counts(planes) -> list:
    """Set bits of each plane of a ``(..., n, h, wd)`` stack, summed over
    any leading (lane) axes, as Python ints (per-row partial sums stay far
    below 2**31).  The count is one fused program on the whole stack, so
    it needs no copy of a plane stack beside it."""
    rows = np.asarray(_row_counts(planes), dtype=np.int64)
    return [int(c) for c in
            rows.reshape(-1, *rows.shape[-2:]).sum(axis=(0, 2))]


def fhp2_frame(planes) -> dict:
    """Mass and momentum of a ``(8, h, wd)`` FHP-II state."""
    counts = plane_counts(planes)[:REST + 1]
    return {"mass": sum(counts),
            "px2": sum(c * v for c, v in zip(counts, CX2)),
            "py": sum(c * v for c, v in zip(counts, CY))}
