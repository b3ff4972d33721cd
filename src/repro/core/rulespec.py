"""Pluggable bit-sliced CA rule specs: one blocked substrate, many automata.

The paper's parallelization machinery -- bit-plane packing, fused
stream+collide launches, tiled word-halo aprons, temporal blocking,
counter-based RNG -- is rule-agnostic; only the collision circuit and the
streaming stencil are FHP-specific.  A :class:`RuleSpec` captures exactly
that per-rule residue:

* ``n_planes``     -- how many bit planes one node carries;
* ``taps``         -- the streaming stencil: which plane moves where, with
                      the row-parity-dependent x offsets of the triangular
                      lattice (``|dx| <= 1``, ``|dy| <= 1``, so every rule
                      honours the kernel's one-row/one-word-per-step halo
                      contract);
* ``collide``      -- the pointwise boolean collision pass over the
                      streamed taps (for FHP, generated from
                      ``core.rules`` -- the same table that builds the
                      LUT; for BML, the two alternating deterministic
                      sub-steps selected by the step parity);
* ``random_words`` -- how many random words the circuit draws per
                      site-step: 0 (BML; the kernel skips the in-kernel
                      hash entirely), 1 (FHP-II's chirality), 2
                      (FHP-III's ``c0``, ``c1``; salts in
                      ``prng.RANDOM_SALTS``);
* ``n_substeps``   -- the sub-step schedule length (BML alternates 2);
* ``solid_plane``  -- index of the static geometry plane, or None for
                      rules without obstacles (gates static-solid mode);
* ``force``        -- the optional body-force pass (FHP only).

Registered rules: ``fhp2`` (FHP-II: 8 planes, one random word, solid
plane 7), ``fhp3`` (FHP-III, collision-saturated: the same layout, two
random words; ``core.rules``) and
``bml`` (Biham--Middleton--Levine traffic: 2 planes, zero RNG, two
alternating deterministic sub-steps -- east cars move on even t, north
cars on odd t, a car advances iff its destination was empty before the
sub-step).  Every spec also carries its *byte oracle*
(``oracle_step``: one full update on a ``(H, W)`` uint8 array) and a
seeded random initial-state builder (``init_bytes``) so the cross-rule
conformance harness (``tests/test_rule_conformance.py``) is fully
rule-parametric.

``step_planes_rule`` / ``run_planes_rule`` are the generic periodic
bit-plane reference steppers (the rule-parametric analogue of
``bitplane.step_planes``); for the FHP specs they are bit-identical to
``bitplane.step_planes`` (conformance-tested), and ``core.distributed``
uses them as its jnp fallback for every rule.
"""
from __future__ import annotations

import dataclasses
import functools
from typing import Callable, Dict, List, Optional, Sequence, Tuple

import jax
import jax.extend as jex
import jax.numpy as jnp
import numpy as np

from repro.core import boolean, prng, rules

_U8 = jnp.uint8


@dataclasses.dataclass(frozen=True)
class Tap:
    """One streaming read: ``plane`` moves by ``offsets[parity]``.

    ``offsets`` is ``((dx_even, dy), (dx_odd, dy))`` -- the
    row-parity-dependent neighbour offsets of the triangular-lattice
    mapping (``rules.OFFSETS``); square-lattice rules use equal pairs.
    The kernel's halo contract requires ``|dx| <= 1`` and ``|dy| <= 1``
    (one apron row / word per side per fused step).
    """

    plane: int
    offsets: Tuple[Tuple[int, int], Tuple[int, int]]

    def __post_init__(self):
        (dx0, dy0), (dx1, dy1) = self.offsets
        assert dy0 == dy1, "the y offset may not depend on row parity"
        assert all(abs(d) <= 1 for d in (dx0, dx1, dy0)), self.offsets


@dataclasses.dataclass(frozen=True)
class RuleSpec:
    """A complete bit-sliced CA rule (see module docstring).

    ``collide(streamed, words, t)`` maps the streamed tap list (one
    array per tap, in ``taps`` order) to the ``n_planes`` output planes;
    it must be pointwise boolean (representation-agnostic: packed uint32
    words or {0,1} arrays).  ``words`` is the tuple of the site-step's
    ``random_words`` random words (empty for a rule that draws none);
    ``t`` is the (possibly traced) global step counter -- the sub-step
    schedule selects on ``t % n_substeps``.

    ``mass_planes`` are the planes whose popcount sum is the conserved
    particle count; ``per_plane_conserved`` claims each mass plane's
    count is *separately* conserved (BML: cars never change species).
    ``exclusive_planes`` declares that at most one of the named planes
    may be set per cell at all times (BML: a cell holds one car) -- a
    *structural* invariant checked without reference values, so it
    catches corruption that happens to preserve counts.
    """

    name: str
    n_planes: int
    taps: Tuple[Tap, ...]
    collide: Callable[[Sequence[jnp.ndarray], Tuple[jnp.ndarray, ...],
                       object], List[jnp.ndarray]]
    random_words: int
    oracle_step: Callable[..., jnp.ndarray]
    init_bytes: Callable[[int, int, float, int], np.ndarray]
    n_substeps: int = 1
    solid_plane: Optional[int] = None
    force: Optional[Callable] = None
    conserves_mass: bool = True
    conserves_momentum: bool = False
    mass_planes: Tuple[int, ...] = ()
    per_plane_conserved: bool = False
    exclusive_planes: Tuple[int, ...] = ()

    def __post_init__(self):
        assert self.n_planes >= 1
        assert 0 <= self.random_words <= len(prng.RANDOM_SALTS), \
            self.random_words
        for tap in self.taps:
            assert 0 <= tap.plane < self.n_planes, tap
        if self.solid_plane is not None:
            # static-solid mode strips the *last* plane from the stack
            assert self.solid_plane == self.n_planes - 1, \
                "the solid plane must be the last plane (static-solid layout)"

    def byte_mask(self) -> int:
        """Mask of the state bits this rule uses in the byte encoding."""
        return (1 << self.n_planes) - 1


_REGISTRY: Dict[str, RuleSpec] = {}


def register_rule(spec: RuleSpec) -> RuleSpec:
    assert spec.name not in _REGISTRY, spec.name
    _REGISTRY[spec.name] = spec
    return spec


def get_rule(name: str) -> RuleSpec:
    try:
        return _REGISTRY[name]
    except KeyError:
        raise ValueError(f"unknown rule {name!r}; "
                         f"registered: {sorted(_REGISTRY)}") from None


def rule_names() -> List[str]:
    return sorted(_REGISTRY)


# ---------------------------------------------------------------------------
# FHP-II / FHP-III: the paper's lattice gases on the pluggable substrate.
# ---------------------------------------------------------------------------

def _fhp_taps() -> Tuple[Tap, ...]:
    taps = [Tap(k, rules.OFFSETS[k]) for k in range(rules.N_DIR)]
    stay = ((0, 0), (0, 0))
    taps.append(Tap(rules.REST_BIT, stay))
    taps.append(Tap(rules.SOLID_BIT, stay))
    return tuple(taps)


def _fhp_spec(variant: str) -> RuleSpec:
    n_words = rules.RANDOM_WORDS[variant]

    def collide(streamed, words, t):
        chi = words[0] if n_words == 1 else tuple(words)
        return boolean.collide_planes(streamed, chi, variant)

    def oracle_step(state, t, chi=None):
        from repro.core import byte_step
        return byte_step.step_bytes(state, t, chi=chi, variant=variant)

    def init_bytes(h, w, density, seed):
        # Bit-identical to the historic Scenario fill (7 bits at density).
        rng = np.random.default_rng(seed)
        occ = (rng.random((7, h, w)) < density).astype(np.uint8)
        state = np.zeros((h, w), dtype=np.uint8)
        for i in range(7):
            state |= occ[i] << i
        return state

    return RuleSpec(
        name=variant, n_planes=8, taps=_fhp_taps(), collide=collide,
        random_words=n_words, oracle_step=oracle_step,
        init_bytes=init_bytes,
        n_substeps=1, solid_plane=rules.SOLID_BIT,
        force=boolean.force_planes,
        conserves_mass=True, conserves_momentum=True,
        mass_planes=tuple(range(7)), per_plane_conserved=False)


# ---------------------------------------------------------------------------
# BML traffic (Biham--Middleton--Levine): two planes, two alternating
# deterministic sub-steps, zero RNG.  Plane 0 = east-bound cars, plane 1
# = north-bound cars (row index increases northward, matching FHP's CY).
# ---------------------------------------------------------------------------

# Tap order for the collision circuit below.  To *read* the neighbour at
# x+1 the tap moves the plane by dx=-1 (the kernel's streamed value at x
# is the source at x-dx); likewise y+1 needs dy=-1.
_BML_TAPS = (
    Tap(0, ((1, 0), (1, 0))),      # E arriving from x-1
    Tap(0, ((0, 0), (0, 0))),      # E in place
    Tap(0, ((-1, 0), (-1, 0))),    # E at x+1  (east-bound occupancy ahead)
    Tap(0, ((0, -1), (0, -1))),    # E at y+1  (north-bound occupancy ahead)
    Tap(1, ((0, 0), (0, 0))),      # N in place
    Tap(1, ((-1, 0), (-1, 0))),    # N at x+1
    Tap(1, ((0, 1), (0, 1))),      # N arriving from y-1
    Tap(1, ((0, -1), (0, -1))),    # N at y+1
)


def _bml_collide(streamed, words, t):
    """One BML sub-step: even t moves east cars, odd t moves north cars.

    A car advances iff its destination cell was empty *before* the
    sub-step (so a convoy opens up one cell per sub-step from the front);
    the other species is frozen.  Pure boolean over the taps -- both
    sub-step outcomes are computed and the (traced) step parity selects.
    """
    eW, e0, eE, eU, n0, nE, nS, nU = streamed
    occ0 = e0 | n0                  # own cell, pre-move
    occ_x1 = eE | nE                # cell at x+1, pre-move
    occ_y1 = eU | nU                # cell at y+1, pre-move
    new_e = (e0 & occ_x1) | (eW & ~occ0)
    new_n = (n0 & occ_y1) | (nS & ~occ0)
    east = (jnp.asarray(t, jnp.int32) % 2) == 0
    return [jnp.where(east, new_e, e0), jnp.where(east, n0, new_n)]


def bml_step_bytes(state: jnp.ndarray, t, chi=None) -> jnp.ndarray:
    """Byte oracle for one BML sub-step on a (H, W) uint8 torus.

    bit 0 = east-bound car, bit 1 = north-bound car; ``chi`` is accepted
    (and ignored) for oracle-signature uniformity.
    """
    s = jnp.asarray(state, _U8)
    e = (s & 1) != 0
    n = (s & 2) != 0
    occ = e | n
    # east sub-step: E cars hop +x where the pre-move destination is empty
    move_e = e & ~jnp.roll(occ, -1, axis=-1)
    e_east = (e & ~move_e) | jnp.roll(move_e, 1, axis=-1)
    # north sub-step: N cars hop +y
    move_n = n & ~jnp.roll(occ, -1, axis=-2)
    n_north = (n & ~move_n) | jnp.roll(move_n, 1, axis=-2)
    east = (jnp.asarray(t, jnp.int32) % 2) == 0
    e_out = jnp.where(east, e_east, e)
    n_out = jnp.where(east, n, n_north)
    return e_out.astype(_U8) | (n_out.astype(_U8) << 1)


def bml_init_bytes(h: int, w: int, density: float, seed: int) -> np.ndarray:
    """Seeded exclusive fill: each cell holds one east car (prob rho/2),
    one north car (prob rho/2), or nothing -- the standard BML ensemble."""
    rng = np.random.default_rng(seed)
    u = rng.random((h, w))
    return np.where(u < density / 2, np.uint8(1),
                    np.where(u < density, np.uint8(2), np.uint8(0)))


register_rule(_fhp_spec("fhp2"))
register_rule(_fhp_spec("fhp3"))
register_rule(RuleSpec(
    name="bml", n_planes=2, taps=_BML_TAPS, collide=_bml_collide,
    random_words=0, oracle_step=bml_step_bytes, init_bytes=bml_init_bytes,
    n_substeps=2, solid_plane=None, force=None,
    conserves_mass=True, conserves_momentum=False,
    mass_planes=(0, 1), per_plane_conserved=True,
    exclusive_planes=(0, 1)))


# ---------------------------------------------------------------------------
# Generic periodic bit-plane reference stepper (rule-parametric analogue
# of ``bitplane.step_planes``; the jnp fallback of ``core.distributed``).
# ---------------------------------------------------------------------------

def stream_taps(planes: jnp.ndarray, taps: Sequence[Tap],
                row0=0) -> List[jnp.ndarray]:
    """Streamed tap values on packed planes (periodic both axes).

    Mirrors the kernel's destination-centric convention: result[i] at
    (y, x) is ``taps[i].plane`` at (y - dy, x - dx) with dx selected by
    the *source* row parity (``row0`` = global row of local row 0)."""
    from repro.core import bitplane
    h = planes.shape[-2]
    parity = ((jnp.arange(h, dtype=jnp.uint32)
               + jnp.asarray(row0, jnp.uint32)) & 1)[:, None]
    even = parity == 0
    out = []
    for tap in taps:
        p = planes[..., tap.plane, :, :]
        (dx0, dy), (dx1, _) = tap.offsets
        if dx0 == dx1:
            moved = bitplane.shift_x(p, dx0)
        else:
            moved = jnp.where(even, bitplane.shift_x(p, dx0),
                              bitplane.shift_x(p, dx1))
        out.append(jnp.roll(moved, dy, axis=-2) if dy else moved)
    return out


def step_planes_rule(planes: jnp.ndarray, t, spec: RuleSpec,
                     p_force: float = 0.0, y0: int = 0, xw0: int = 0, *,
                     words=None, accel=None) -> jnp.ndarray:
    """One fused update of ``spec`` on packed ``(..., n_planes, H, Wd)``
    planes -- stream the taps, run the collision circuit, apply the
    optional force pass.  ``words`` overrides the counter-based random
    words (a tuple of ``spec.random_words``).  For the FHP specs this is
    bit-identical to ``bitplane.step_planes`` (conformance-tested)."""
    assert planes.shape[-3] == spec.n_planes, \
        (planes.shape, spec.name, spec.n_planes)
    shape_words = planes.shape[-2:]
    streamed = stream_taps(planes, spec.taps, row0=y0)
    if words is None:
        words = prng.random_words(shape_words, t, spec.random_words,
                                  y0=y0, xw0=xw0)
    out = spec.collide(streamed, tuple(words), t)
    if p_force or accel is not None:
        assert spec.force is not None, \
            f"rule {spec.name!r} has no force pass"
        if accel is None:
            accel = prng.bernoulli_words(shape_words, t, p_force,
                                         y0=y0, xw0=xw0)
        out = spec.force(out, accel)
    return jnp.stack(out, axis=-3)


def run_planes_rule(planes: jnp.ndarray, steps: int, spec: RuleSpec,
                    p_force: float = 0.0, t0: int = 0) -> jnp.ndarray:
    def body(i, s):
        return step_planes_rule(s, t0 + i, spec, p_force)
    return jax.lax.fori_loop(0, int(steps), body, planes)


# ---------------------------------------------------------------------------
# Invariant audits: every registered rule carries exact conservation laws,
# so corruption of a packed state is detectable *for free* by popcount
# reductions -- no reference run needed.  The serve layer audits these per
# cadence and treats any violation as a corruption signal (rollback).
# ---------------------------------------------------------------------------

def _pop(p: jnp.ndarray) -> jnp.ndarray:
    dt = jnp.int64 if jax.config.jax_enable_x64 else jnp.int32
    return jax.lax.population_count(p).sum(axis=(-2, -1), dtype=dt)


def invariants(spec: RuleSpec, planes: jnp.ndarray, *,
               with_momentum: bool = False) -> Dict[str, jnp.ndarray]:
    """Per-lane conserved quantities of ``spec`` on packed
    ``(..., n_planes, H, Wd)`` planes (leading axes = ensemble lanes).

    Keys: ``mass`` (popcount sum over ``mass_planes``); ``plane{i}`` per
    mass plane when ``per_plane_conserved`` (BML species counts);
    ``solid`` (the static geometry plane's popcount -- the update never
    touches it, so it is conserved for any rule that has one);
    ``px2``/``py`` (doubled-x / y momentum) when ``with_momentum`` and
    the rule conserves momentum.  Momentum is only an invariant on a
    free torus -- callers must not request it for states with solid
    sites or under forcing (bounce-back and the body force both inject
    momentum by design)."""
    assert planes.shape[-3] == spec.n_planes, (planes.shape, spec.name)
    out: Dict[str, jnp.ndarray] = {}
    if spec.conserves_mass and spec.mass_planes:
        counts = [_pop(planes[..., i, :, :]) for i in spec.mass_planes]
        out["mass"] = sum(counts[1:], counts[0])
        if spec.per_plane_conserved:
            for i, c in zip(spec.mass_planes, counts):
                out[f"plane{i}"] = c
    if spec.solid_plane is not None:
        out["solid"] = _pop(planes[..., spec.solid_plane, :, :])
    if with_momentum and spec.conserves_momentum:
        px2 = jnp.zeros(planes.shape[:-3], jnp.int32)
        py = jnp.zeros(planes.shape[:-3], jnp.int32)
        for i in range(rules.N_DIR):
            c = _pop(planes[..., i, :, :]).astype(jnp.int32)
            px2 = px2 + c * int(rules.CX2[i])
            py = py + c * int(rules.CY[i])
        out["px2"], out["py"] = px2, py
    return out


def integrity_ok(spec: RuleSpec, planes: jnp.ndarray) -> jnp.ndarray:
    """Per-lane boolean: the *structural* invariants hold (currently
    ``exclusive_planes`` -- no cell carries two exclusive species).
    Unlike :func:`invariants` this needs no reference values, so it
    catches compensating corruption that preserves every count."""
    ok = jnp.ones(planes.shape[:-3], bool)
    exc = spec.exclusive_planes
    for a in range(len(exc)):
        for b in range(a + 1, len(exc)):
            overlap = planes[..., exc[a], :, :] & planes[..., exc[b], :, :]
            ok = ok & (_pop(overlap) == 0)
    return ok


def audit(spec: RuleSpec, planes: jnp.ndarray, expected: Dict[str, object],
          *, with_momentum: bool = False) -> Dict[str, Tuple]:
    """Compare a state's invariants against ``expected`` (the values
    recorded at admission / last audited checkpoint).

    Returns ``{name: (expected, found)}`` for every violated invariant
    (empty dict == clean).  ``integrity`` appears with expected ``True``
    when a structural check fails.  Works on single-lane states; for
    batched lanes audit each lane's slice (the serve engine does)."""
    found = invariants(spec, planes, with_momentum=with_momentum)
    bad = {}
    for name, want in expected.items():
        if name not in found:
            continue
        got = found[name]
        if not bool((got == jnp.asarray(want)).all()):
            bad[name] = (np.asarray(want).tolist(),
                         np.asarray(got).tolist())
    if not bool(integrity_ok(spec, planes).all()):
        bad["integrity"] = (True, False)
    return bad


# ---------------------------------------------------------------------------
# Fused in-kernel moments: the static description of what the Pallas
# kernel accumulates per block while the planes sit in VMEM.
#
# Every moment is a linear combination of *term* popcounts, where a term
# is either one plane (``(p,)``) or the AND of two planes (``(a, b)`` --
# the structural-exclusivity overlap, expected 0).  The same
# :class:`MomentSpec` drives three bit-identical computations: the
# kernel's per-block SWAR accumulation (``kernels/fhp_step/kernel.py``),
# the post-hoc reference (:func:`compute_moments`, the popcount path the
# bit-exactness gate compares against), and the serve engine's audits
# (the moment rows are named to match :func:`invariants` keys, so the
# fused output replaces the per-cadence invariant re-stream for free).
# All accumulation is int32 (the kernel's native width);
# :func:`require_moment_headroom` refuses lattices whose worst-case
# moment could overflow it.
# ---------------------------------------------------------------------------


@dataclasses.dataclass(frozen=True)
class MomentSpec:
    """Static moment layout: ``moments = coeffs @ popcount(terms)``.

    ``names[r]`` labels row ``r`` (``mass``, ``plane{i}``, ``solid``,
    ``px2``, ``py``, ``excl{a}_{b}``); ``terms[t]`` is ``(p,)`` (plane
    popcount) or ``(a, b)`` (pairwise-AND popcount); ``coeffs[r][t]``
    the int weight of term ``t`` in row ``r``.  Hashable (static kernel
    parameter)."""

    names: Tuple[str, ...]
    terms: Tuple[Tuple[int, ...], ...]
    coeffs: Tuple[Tuple[int, ...], ...]

    @property
    def n_moments(self) -> int:
        return len(self.names)

    @property
    def n_terms(self) -> int:
        return len(self.terms)

    def row(self, name: str) -> int:
        return self.names.index(name)


def moment_spec(spec: RuleSpec,
                stack_planes: Optional[int] = None) -> MomentSpec:
    """The :class:`MomentSpec` of ``spec`` on a ``stack_planes``-plane
    stack (default ``spec.n_planes``; pass ``n_planes - 1`` for the
    static-solid dynamic stack, which drops the ``solid`` row -- the
    cached solid plane is constant, so its popcount needs no in-kernel
    accumulation)."""
    np_ = spec.n_planes if stack_planes is None else stack_planes
    terms: List[Tuple[int, ...]] = []

    def term(t: Tuple[int, ...]) -> int:
        if t not in terms:
            terms.append(t)
        return terms.index(t)

    rows: List[Tuple[str, Dict[int, int]]] = []
    if spec.conserves_mass and spec.mass_planes:
        rows.append(("mass", {term((p,)): 1 for p in spec.mass_planes}))
        if spec.per_plane_conserved:
            for p in spec.mass_planes:
                rows.append((f"plane{p}", {term((p,)): 1}))
    if spec.solid_plane is not None and spec.solid_plane < np_:
        rows.append(("solid", {term((spec.solid_plane,)): 1}))
    if spec.conserves_momentum:
        rows.append(("px2", {term((i,)): int(rules.CX2[i])
                             for i in range(rules.N_DIR)}))
        rows.append(("py", {term((i,)): int(rules.CY[i])
                            for i in range(rules.N_DIR)}))
    exc = spec.exclusive_planes
    for a in range(len(exc)):
        for b in range(a + 1, len(exc)):
            rows.append((f"excl{exc[a]}_{exc[b]}",
                         {term((exc[a], exc[b])): 1}))
    for t in terms:
        assert all(p < np_ for p in t), (t, np_, spec.name)
    coeffs = tuple(tuple(row.get(ti, 0) for ti in range(len(terms)))
                   for _, row in rows)
    return MomentSpec(names=tuple(n for n, _ in rows),
                      terms=tuple(terms), coeffs=coeffs)


def compute_moments(planes: jnp.ndarray, ms: MomentSpec) -> jnp.ndarray:
    """Post-hoc reference: the moments of packed ``(..., P, H, Wd)``
    planes as ``(..., n_moments)`` **int32** (leading axes = ensemble
    lanes).  Bit-identical to the kernel's fused accumulation -- fixed
    int32 regardless of the x64 flag, matching the kernel's native
    accumulator width (``require_moment_headroom`` guards overflow)."""
    vals = []
    for t in ms.terms:
        p = planes[..., t[0], :, :]
        if len(t) == 2:
            p = p & planes[..., t[1], :, :]
        vals.append(jax.lax.population_count(p).sum(
            axis=(-2, -1), dtype=jnp.int32))
    tv = jnp.stack(vals, axis=-1)                       # (..., n_terms)
    c = jnp.asarray(ms.coeffs, jnp.int32)               # (rows, terms)
    return (tv[..., None, :] * c).sum(axis=-1, dtype=jnp.int32)


def moments_dict(ms: MomentSpec, values) -> Dict[str, object]:
    """``{name: values[..., r]}`` view of a moments array/record."""
    return {name: values[..., r] for r, name in enumerate(ms.names)}


def moment_headroom(ms: MomentSpec, n_sites: int) -> int:
    """Worst-case |moment| on an ``n_sites``-node lattice (every term
    popcount is at most ``n_sites``)."""
    return max((sum(abs(c) for c in row) for row in ms.coeffs), default=0) \
        * n_sites


def require_moment_headroom(ms: MomentSpec, n_sites: int) -> None:
    """Refuse moment accumulation that could overflow int32: the fused
    path (and :func:`compute_moments`) accumulate in the kernel's native
    int32, so a lattice whose worst-case moment reaches 2**31 must fall
    back to the post-hoc int64 ``invariants`` path instead of silently
    wrapping."""
    worst = moment_headroom(ms, n_sites)
    if worst >= 2 ** 31:
        raise ValueError(
            f"moment accumulator overflow: worst-case |moment| {worst} "
            f">= 2**31 on a {n_sites}-site lattice (int32 in-kernel "
            f"accumulation); use the post-hoc invariants path")


def oracle_run(state, steps: int, spec: RuleSpec, t0: int = 0):
    """Advance the byte oracle ``steps`` steps, drawing the *word-RNG*
    random words (expanded to bytes: ``r = sum(bit of word k << k)``) for
    rules that draw any -- so the oracle is bit-comparable with the
    packed/Pallas paths at any T."""
    s = jnp.asarray(state)
    h, w = s.shape[-2:]
    shifts = jnp.arange(32, dtype=jnp.uint32)
    for k in range(int(steps)):
        chi = None
        if spec.random_words:
            chi = jnp.zeros((h, w), _U8)
            words = prng.random_words((h, w // 32), t0 + k,
                                      spec.random_words)
            for i, word in enumerate(words):
                bits = ((word[..., None] >> shifts) & 1).astype(_U8)
                chi = chi | (bits.reshape(h, w) << i)
        s = spec.oracle_step(s, t0 + k, chi=chi)
    return s


@functools.lru_cache(maxsize=None)
def _ops_per_word_step(name: str, pq: int) -> int:
    spec = get_rule(name)
    p_force = pq / (1 << prng.BERNOULLI_BITS)
    block = (8, 128)                       # one (sublane, lane) vreg tile

    def body(planes, rows, cols, t):
        streamed = stream_taps(planes, spec.taps)
        words = prng.random_words_at(rows, cols, t, spec.random_words)
        out = spec.collide(streamed, words, t)
        if pq:
            out = spec.force(out, prng.bernoulli_words_at(rows, cols, t,
                                                          p_force))
        return out

    u32 = jnp.uint32
    jaxpr = jax.make_jaxpr(body)(
        jax.ShapeDtypeStruct((spec.n_planes,) + block, u32),
        jax.ShapeDtypeStruct((block[0], 1), u32),
        jax.ShapeDtypeStruct((1, block[1]), u32),
        jax.ShapeDtypeStruct((), u32))
    return _count_eqns(jaxpr.jaxpr)


def _count_eqns(jaxpr) -> int:
    """Equations of ``jaxpr``, those of nested jaxprs (pjit, ...) counted
    in place of the equation that holds them."""
    n = 0
    for eqn in jaxpr.eqns:
        subs = [v for v in eqn.params.values()
                if isinstance(v, (jex.core.Jaxpr, jex.core.ClosedJaxpr))]
        if subs:
            n += sum(_count_eqns(getattr(j, "jaxpr", j)) for j in subs)
        else:
            n += 1
    return n


def ops_per_word_step(spec: RuleSpec, p_force: float = 0.0) -> int:
    """Operations per packed word per CA step of ``spec``: the equations
    in the jaxpr of one step's body -- streaming the taps, drawing the
    random words, the collision circuit and, when ``p_force`` > 0, the
    forcing coin and pass -- traced on one uint32 word tile.  Every
    equation is one VPU operation per word, so this is the numerator of
    the kernel's VPU roofline; the benchmark reads it from the kernel's
    ``kernel.build`` telemetry event."""
    return _ops_per_word_step(spec.name, prng.quantize_p(p_force))
