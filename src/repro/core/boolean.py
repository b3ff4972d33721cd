"""Branchless boolean FHP collision algebra, generated from the rule table.

The paper implements scattering as a 256-entry LUT (one gather per node).
Per-element gathers are catastrophic on the TPU VPU, so the TPU-native
formulation evaluates the *same* rule table as pure AND/OR/NOT/XOR over bit
planes: every bit lane of every word is an independent lattice node, so a
``(H, W/32)`` uint32 array processes 32 nodes per lane x (8, 128) lanes per
vector op -- the faithful analogue of the paper's 32-nodes-per-AVX-register.

``collide_planes`` is generated *from* the rule tables in ``rules``
(``fhp2_rules()``, ``fhp3_table()``: the same sources as the LUT), so LUT
path == boolean path is checked by construction in the tests, not by
hand-derived algebra.

The functions are representation-agnostic: inputs may be packed uint32 words
(32 nodes/lane) or {0,1}-valued arrays of any integer dtype (1 node/lane);
every AND-chain contains at least one positive literal, so values stay in
the lanes they started in.
"""
from __future__ import annotations

import functools
from typing import List, Sequence

import jax.numpy as jnp

from repro.core import rules


def _cond(a: Sequence[jnp.ndarray], r: rules.Rule) -> jnp.ndarray:
    """Exact-match condition of one rule over the moving planes (+ rest)."""
    # Start from a positive literal to keep high bit-lanes clean.
    pos = sorted(r.moving_in)
    c = a[pos[0]]
    for i in pos[1:]:
        c = c & a[i]
    for i in range(rules.N_DIR):
        if i not in r.moving_in:
            c = c & ~a[i]
    if r.rest_in is True:
        c = c & a[rules.REST_BIT]
    elif r.rest_in is False:
        c = c & ~a[rules.REST_BIT]
    return c


def collide_planes(planes: Sequence[jnp.ndarray], chi,
                   variant: str = "fhp2") -> List[jnp.ndarray]:
    """Apply FHP collisions to 8 bit planes.

    planes: [a0..a5 moving, rest, solid]; returns the same layout.
    ``chi`` is the site's random bits: the chirality word for FHP-II, the
    pair of words ``(c0, c1)`` for FHP-III.
    Solid lanes get full bounce-back (i -> i+3), rest/solid unchanged there.
    The algebra is generated from the variant's table in ``rules`` -- the
    same table that builds the LUT, so the two paths agree by construction.
    """
    a = list(planes)
    if variant == "fhp3":
        c0, c1 = chi
        new = _collide_fhp3(a, c0, c1)
        return _fluid_or_bounce(a, new[:rules.N_DIR], new[rules.REST_BIT])
    if variant != "fhp2":
        raise ValueError(variant)
    rs = rules.fhp2_rules()
    conds = [_cond(a, r) for r in rs]

    fired = conds[0]
    for c in conds[1:]:
        fired = fired | c

    new_mov: List[jnp.ndarray] = []
    for j in range(rules.N_DIR):
        acc = a[j] & ~fired
        for r, c in zip(rs, conds):
            in0 = j in r.out_c0
            in1 = j in r.out_c1
            if in0 and in1:
                acc = acc | c
            elif in0:
                acc = acc | (c & ~chi)
            elif in1:
                acc = acc | (c & chi)
        new_mov.append(acc)

    clear = None
    set_ = None
    for r, c in zip(rs, conds):
        if r.rest_out is False:
            clear = c if clear is None else (clear | c)
        elif r.rest_out is True:
            set_ = c if set_ is None else (set_ | c)
    new_rest = a[rules.REST_BIT]
    if clear is not None:
        new_rest = new_rest & ~clear
    if set_ is not None:
        new_rest = new_rest | set_
    return _fluid_or_bounce(a, new_mov, new_rest)


def _fluid_or_bounce(a, new_mov, new_rest) -> List[jnp.ndarray]:
    """The collided fluid planes on fluid lanes; bounce-back of every
    mover, rest and solid unchanged, on solid lanes."""
    solid = a[rules.SOLID_BIT]
    out: List[jnp.ndarray] = []
    for j in range(rules.N_DIR):
        bounced = solid & a[rules.opposite(j)]
        out.append(bounced | (~solid & new_mov[j]))
    out.append((solid & a[rules.REST_BIT]) | (~solid & new_rest))
    out.append(solid)
    return out


def _collide_fhp3(a, c0, c1) -> List[jnp.ndarray]:
    """FHP-III's 7 fluid planes after collision (``rules.fhp3_table``).

    Each colliding state's 7-literal minterm is the AND of two shared
    partial decodes (fluid bits 0-3 and 4-6), so the 76 minterms cost one
    AND each beyond the decoders.  Output bit j of a colliding state
    depends on the random index ``r = c0 + 2 * c1`` through a 4-entry
    truth table (bit r set iff output r has bit j); the minterms that
    share plane j and truth table are ORed first and selected once.
    Every minterm holds a positive literal (a colliding state has two
    particles or more), so {0,1}-valued inputs stay in their lanes.
    """
    n = rules.REST_BIT + 1
    neg = {}
    decode = {}

    def lit(i, v):
        if v:
            return a[i]
        if i not in neg:
            neg[i] = ~a[i]
        return neg[i]

    def term(bits, value):
        """Minterm of fluid bits ``bits`` (ascending) equal to ``value``."""
        key = (bits, sum(value & (1 << b) for b in bits))
        if key not in decode:
            head = lit(bits[-1], (value >> bits[-1]) & 1)
            decode[key] = head if len(bits) == 1 else \
                term(bits[:-1], value) & head
        return decode[key]

    table = rules.fhp3_table()
    minterm = {s: term((0, 1, 2, 3), s) & term((4, 5, 6), s) for s in table}
    fired = functools.reduce(jnp.bitwise_or, minterm.values())

    nc0, nc1 = ~c0, ~c1
    one = {0: nc0 & nc1, 1: c0 & nc1, 2: nc0 & c1, 3: c0 & c1}
    select = {0b1010: c0, 0b0101: nc0, 0b1100: c1, 0b0011: nc1}

    def pick(tt):
        if tt not in select:
            select[tt] = functools.reduce(
                jnp.bitwise_or, [one[r] for r in range(4) if (tt >> r) & 1])
        return select[tt]

    not_fired = ~fired
    new = []
    for j in range(n):
        groups = {}
        for s, outs in table.items():
            tt = sum(((o >> j) & 1) << r for r, o in enumerate(outs))
            if tt:
                groups.setdefault(tt, []).append(minterm[s])
        acc = a[j] & not_fired
        for tt in sorted(groups):
            g = functools.reduce(jnp.bitwise_or, groups[tt])
            acc = acc | (g if tt == 0b1111 else g & pick(tt))
        new.append(acc)
    return new


def force_planes(planes: Sequence[jnp.ndarray], accel: jnp.ndarray) -> List[jnp.ndarray]:
    """Body force on planes: reverse W-movers into E-movers where ``accel``."""
    a = list(planes)
    cond = a[3] & ~a[0] & ~a[rules.SOLID_BIT] & accel
    a[3] = a[3] ^ cond
    a[0] = a[0] | cond
    return a
