"""FHP rule system: directions, lattice offsets, collision tables, LUT builder.

State encoding (paper Fig. 1): bits 0-5 = moving particles along the six
triangular-lattice directions, bit 6 = rest particle, bit 7 = solid/boundary
flag.  A node state is one byte.

Direction layout (angle = 60 deg * i, y points "north"):

    i : 0=E, 1=NE, 2=NW, 3=W, 4=SW, 5=SE

Doubled integer coordinates keep momentum arithmetic exact:
    c_i = (cx2[i]/2, cy[i]*sqrt(3)/2);  we track (cx2, cy) integers.

The triangular lattice is mapped onto a rectangular array (paper Fig. 3) with
odd rows shifted right by half a lattice constant.  Neighbour x-offsets then
depend on the row parity of the *source* node; see OFFSETS.

Two collision tables:

* FHP-II (``fhp2_rules``): head-on pairs, symmetric triples, two head-on
  pairs and the rest-particle exchange, one chirality bit per site;
* FHP-III (``fhp3_table``), collision-saturated (Frisch, d'Humieres,
  Hasslacher, Lallemand, Pomeau, Rivet, Complex Systems 1, 649, 1987):
  the 128 fluid states fall into classes of equal (mass, sum cx2, sum
  cy); every state whose class has another member collides, into one of
  the other members.  12 classes of 2, 14 of 3 and 2 of 5: 76 colliding
  states.  Two random bits per site, ``c0`` and ``c1``, pick the member:
  ``r = c0 + 2 * c1`` indexes the other members in ascending order of
  state value, modulo their number (a class of 3 reads ``c0`` alone, a
  class of 2 neither).  The published rule fixes the classes; this
  indexing is the repository's convention.

Solid sites bounce every mover back (i -> i+3) and keep the rest particle,
under both tables.
"""
from __future__ import annotations

import dataclasses
from functools import lru_cache
from typing import Dict, FrozenSet, Optional, Tuple

import numpy as np

N_DIR = 6
REST_BIT = 6
SOLID_BIT = 7
MOVING_MASK = 0x3F
REST_MASK = 1 << REST_BIT
SOLID_MASK = 1 << SOLID_BIT

# Doubled x-momentum and (unit sqrt(3)/2) y-momentum per direction.
CX2 = np.array([2, 1, -1, -2, -1, 1], dtype=np.int64)
CY = np.array([0, 1, 1, 0, -1, -1], dtype=np.int64)

# OFFSETS[k][parity] = (dx, dy) of the neighbour a particle moving along k
# reaches, where parity = source row index & 1 (odd rows shifted right).
OFFSETS: Tuple[Tuple[Tuple[int, int], Tuple[int, int]], ...] = (
    ((1, 0), (1, 0)),      # 0 E
    ((0, 1), (1, 1)),      # 1 NE
    ((-1, 1), (0, 1)),     # 2 NW
    ((-1, 0), (-1, 0)),    # 3 W
    ((-1, -1), (0, -1)),   # 4 SW
    ((0, -1), (1, -1)),    # 5 SE
)


def opposite(i: int) -> int:
    return (i + 3) % N_DIR


def rotate_set(dirs: FrozenSet[int], by: int) -> FrozenSet[int]:
    return frozenset((d + by) % N_DIR for d in dirs)


@dataclasses.dataclass(frozen=True)
class Rule:
    """One exact-match collision rule.

    A rule fires on a fluid node whose *moving* bit set equals
    ``moving_in`` and (if ``rest_in`` is not None) whose rest bit equals
    ``rest_in``.  ``out_c0``/``out_c1`` are the two chirality-resolved
    output moving sets (equal when the rule is achiral); ``rest_out`` is
    the new rest bit, None for "unchanged".
    """

    moving_in: FrozenSet[int]
    rest_in: Optional[bool]
    out_c0: FrozenSet[int]
    out_c1: FrozenSet[int]
    rest_out: Optional[bool]
    name: str


def fhp2_rules() -> Tuple[Rule, ...]:
    """The FHP-II rule table (2-body, 3-body, 4-body, rest exchange)."""
    rules = []
    # Two-body head-on: {i, i+3} -> rotate the pair by +/-60deg.  The rest
    # particle, if present, is a spectator (rest_in=None).
    for i in range(3):
        pair = frozenset({i, opposite(i)})
        rules.append(Rule(pair, None, rotate_set(pair, 1), rotate_set(pair, -1),
                          None, f"head-on-{i}"))
    # Three-body symmetric: {i, i+2, i+4} -> the complementary triple.
    for i in range(2):
        tri = frozenset({i, (i + 2) % 6, (i + 4) % 6})
        rules.append(Rule(tri, None, rotate_set(tri, 1), rotate_set(tri, 1),
                          None, f"triple-{i}"))
    # Four-body (two head-on pairs): particle-hole dual of 2-body.
    for i in range(3):
        quad = frozenset({i, (i + 1) % 6, opposite(i), (opposite(i) + 1) % 6})
        rules.append(Rule(quad, None, rotate_set(quad, 1), rotate_set(quad, -1),
                          None, f"four-body-{i}"))
    # Rest exchange: {i} + rest <-> {i-1, i+1}.  c_{i-1}+c_{i+1} = c_i.
    for i in range(N_DIR):
        single = frozenset({i})
        split = frozenset({(i - 1) % 6, (i + 1) % 6})
        rules.append(Rule(single, True, split, split, False, f"rest-split-{i}"))
        rules.append(Rule(split, False, single, single, True, f"rest-merge-{i}"))
    return tuple(rules)


def fhp2_table() -> Dict[int, Tuple[int, int]]:
    """FHP-II: each colliding fluid state -> its output for chirality 0
    and 1 (``fhp2_rules``, whose exact-match patterns must not overlap)."""
    table = {}
    for r in fhp2_rules():
        for rest in ([r.rest_in] if r.rest_in is not None else [False, True]):
            s = _set_to_bits(r.moving_in) | (REST_MASK if rest else 0)
            if s in table:
                raise ValueError(f"rule overlap: {r.name} at state {s}")
            rest_new = rest if r.rest_out is None else r.rest_out
            table[s] = tuple(_set_to_bits(out) | (REST_MASK if rest_new
                                                  else 0)
                             for out in (r.out_c0, r.out_c1))
    return table


# Random bits a site-step draws, per FHP variant.
RANDOM_WORDS = {"fhp2": 1, "fhp3": 2}


def fluid_classes() -> Tuple[Tuple[int, ...], ...]:
    """The 128 fluid states grouped by (mass, sum cx2, sum cy), each class
    in ascending order of state value, the classes ordered by their first
    member."""
    groups = {}
    for s in range(REST_MASK << 1):
        groups.setdefault((mass_of(s),) + momentum_of(s), []).append(s)
    return tuple(sorted(tuple(g) for g in groups.values()))


@lru_cache(maxsize=None)
def fhp3_table() -> Dict[int, Tuple[int, int, int, int]]:
    """FHP-III: each colliding fluid state -> its output for ``r = c0 + 2
    * c1`` = 0, 1, 2, 3 (module docstring)."""
    table = {}
    for cls in fluid_classes():
        for s in cls:
            others = [o for o in cls if o != s]
            if others:
                table[s] = tuple(others[r % len(others)] for r in range(4))
    return table


def _set_to_bits(s: FrozenSet[int]) -> int:
    out = 0
    for d in s:
        out |= 1 << d
    return out


def mass_of(state: int) -> int:
    return bin(state & (MOVING_MASK | REST_MASK)).count("1")


def momentum_of(state: int) -> Tuple[int, int]:
    px2 = 0
    py = 0
    for i in range(N_DIR):
        if state & (1 << i):
            px2 += int(CX2[i])
            py += int(CY[i])
    return px2, py


def bounce_back(state: int) -> int:
    """Full bounce-back of the moving bits (i -> i+3); rest/solid unchanged."""
    m = state & MOVING_MASK
    rev = ((m >> 3) | (m << 3)) & MOVING_MASK
    return (state & ~MOVING_MASK & 0xFF) | rev


@lru_cache(maxsize=None)
def build_lut(variant: str = "fhp2") -> np.ndarray:
    """Build the ``(2 ** RANDOM_WORDS[variant], 256)`` collision LUT: axis
    0 is the site's random bits ``r`` (FHP-II: the chirality bit; FHP-III:
    ``c0 + 2 * c1``).

    Verifies mass and momentum conservation for every fluid entry and
    mass conservation + momentum reversal for solid entries.
    """
    n_rows = 1 << RANDOM_WORDS[variant]
    table = fhp2_table() if variant == "fhp2" else fhp3_table()
    lut = np.zeros((n_rows, 256), dtype=np.uint8)
    for s in range(256):
        if s & SOLID_MASK:
            lut[:, s] = bounce_back(s)
        else:
            lut[:, s] = table.get(s, s)

    # --- conservation audit (runs once, cached) ---
    for chi in range(n_rows):
        for s in range(256):
            o = int(lut[chi, s])
            if s & SOLID_MASK:
                assert o & SOLID_MASK, (chi, s, o)
                assert mass_of(o & 0x7F) == mass_of(s & 0x7F), (chi, s, o)
                pin, pout = momentum_of(s), momentum_of(o)
                assert pout == (-pin[0], -pin[1]), (chi, s, o)
            else:
                assert not (o & SOLID_MASK)
                assert mass_of(o) == mass_of(s), (chi, s, o)
                assert momentum_of(o) == momentum_of(s), (chi, s, o)
    return lut


def lut_flat(variant: str = "fhp2") -> np.ndarray:
    """LUT flattened with index = r << 8 | state (``build_lut``)."""
    return build_lut(variant).reshape(-1).copy()
