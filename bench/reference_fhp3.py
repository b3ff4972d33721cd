"""Plain reference stepper for the FHP-III configuration's check.

Written from the rule's definition alone, in straightforward
``jax.numpy`` over packed bit planes; of the benchmark it takes only
``bench/reference.py``'s hash, random stream, forcing coin, fill and
shift helpers, and nothing of the system under test.

FHP-III (Frisch, d'Humieres, Hasslacher, Lallemand, Pomeau, Rivet,
"Lattice gas hydrodynamics in two and three dimensions", Complex Systems
1, 649, 1987) is collision-saturated: the fluid states of a site (six
movers and a rest particle, 128 in all) fall into classes of equal mass
and momentum, and every state whose class has another member collides
into one of the other members.  The classes are enumerated here from
the directions' momenta ``CX2`` and ``CY``.  Which member: the site's
two random bits ``c0`` (the chirality word, salt 0x11) and ``c1`` (salt
0x33) give ``r = c0 + 2 * c1``, and the state goes to the ``r mod k``-th
of its class's ``k`` other members in ascending order of state value
(the configuration's convention; the published rule fixes the classes).

Solid sites (plane 7) bounce every mover back and keep the rest
particle; the body force then turns a west mover into an east mover on a
fluid site whose east channel is empty, where a Bernoulli(p) coin says
so.  Layout and streaming are ``bench/reference.py``'s.

``run_band`` keeps ``reference.run_band``'s contract.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

from bench.reference import (CHIRALITY_SALT, CX2, CY, MOVES, N_DIR, REST,
                             SOLID, force_words, random_words, shift_x)

C1_SALT = 0x33
FLUID = N_DIR + 1                      # planes 0-5 movers, 6 the rest


def classes():
    """The classes of fluid states with more than one member, each as
    the ascending tuple of its states (bit d of a state is plane d)."""
    by_key = {}
    for s in range(1 << FLUID):
        bits = [(s >> d) & 1 for d in range(FLUID)]
        key = (sum(bits), sum(b * c for b, c in zip(bits, CX2)),
               sum(b * c for b, c in zip(bits, CY)))
        by_key.setdefault(key, []).append(s)
    return [tuple(g) for g in by_key.values() if len(g) > 1]


def table():
    """``{state: (output for r = 0, 1, 2, 3)}`` over the colliding
    fluid states."""
    out = {}
    for cls in classes():
        for s in cls:
            others = [o for o in cls if o != s]
            out[s] = tuple(others[r % len(others)] for r in range(4))
    return out


def fhp3_collide(a, c0, c1):
    """FHP-III collision of the 8 streamed planes, bounce-back on solids."""
    ones = jnp.full_like(a[0], 0xFFFFFFFF)
    pick = [(~c0 if r & 1 == 0 else c0) & (~c1 if r & 2 == 0 else c1)
            for r in range(4)]
    fired = jnp.zeros_like(a[0])
    new = [jnp.zeros_like(a[0]) for _ in range(FLUID)]
    for s, outs in table().items():
        hit = ones
        for d in range(FLUID):
            hit = hit & (a[d] if (s >> d) & 1 else ~a[d])
        fired = fired | hit
        for r, o in enumerate(outs):
            for d in range(FLUID):
                if (o >> d) & 1:
                    new[d] = new[d] | (hit & pick[r])
    solid = a[SOLID]
    out = []
    for d in range(N_DIR):
        fluid = (a[d] & ~fired) | new[d]
        out.append((solid & a[(d + 3) % N_DIR]) | (~solid & fluid))
    rest = (a[REST] & ~fired) | new[REST]
    out.append((solid & a[REST]) | (~solid & rest))
    out.append(solid)
    return out


def fhp3_step(planes, t, rows, p_force: float):
    """One FHP-III step of ``(8, h, wd)`` planes whose row ``i`` is global
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
    out = fhp3_collide(streamed,
                       random_words(rows, cols, t, CHIRALITY_SALT),
                       random_words(rows, cols, t, C1_SALT))
    if p_force:
        push = out[3] & ~out[0] & ~out[SOLID] & force_words(rows, cols, t,
                                                            p_force)
        out[3] = out[3] ^ push
        out[0] = out[0] | push
    return jnp.stack(out)


def run_rows(planes, t0, rows, steps: int, p_force: float):
    """``steps`` FHP-III steps from global time ``t0`` (traced) of
    ``planes`` whose rows are the global rows ``rows`` ((h, 1) uint32)."""
    return jax.lax.fori_loop(
        0, steps, lambda i, s: fhp3_step(s, t0 + i, rows, p_force), planes)


def run_band(ext, glob, t0, *, steps: int, rule: str, p_force: float):
    """The middle rows of the band ``ext`` (8, rows + 2 * steps, Wd),
    whose rows are the global rows ``glob`` (``reference.band_rows(h, r0,
    rows, steps)``), after ``steps`` FHP-III steps from time ``t0``: the
    band is advanced as if periodic and the apron of ``steps`` rows on
    each side, as far as the wrap-around error can reach, is cut away."""
    if rule != "fhp3":
        raise ValueError(f"this reference steps fhp3, not {rule!r}")
    out = run_rows(ext, t0, glob.astype(jnp.uint32)[:, None], steps,
                   p_force)
    return out[:, steps:out.shape[1] - steps]

