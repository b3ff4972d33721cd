"""Pallas TPU kernel: fused, temporally-blocked FHP stream + collide (+ force).

This is the TPU-native translation of the paper's two hot loops:

* the AVX "motion" kernel (Listing 1) -- here the x-component of streaming
  is a lane-local bit shift with cross-word carry and the y-component is a
  row selection from an overlapping halo block;
* the LUT "scattering" pass -- here the branchless boolean collision algebra
  generated from the same FHP-II rule table (see ``core/boolean.py``).

The paper streams the whole lattice to memory twice per time step (motion
pass + scattering pass).  Fusing both into one Pallas kernel halves HBM
traffic -- the dominant cost of this memory-bound algorithm -- and is the
first beyond-paper optimization recorded in EXPERIMENTS.md section Perf.

Temporal blocking (EXPERIMENTS.md section Perf, stage 3): the kernel
advances ``steps`` = T full stream->collide->force updates per launch.  The
row-band halo widens from 1 to T rows; every unrolled step consumes one
halo row from each side (redundant "apron" compute, the time-extended
version of the paper's overlapping CUDA blocks in Figs. 7/8), so after T
steps exactly the program's own disjoint ``bh``-row band is valid and is
written back.  The plane stack then crosses HBM once per T steps instead of
once per step -- a T-fold cut of the dominant cost.  Redundant halo compute
stays exact because the counter-based RNG is a pure function of the global
``(row, word, t)`` coordinates: two programs recomputing the same halo row
draw identical bits.

Batched ensemble lanes: the grid is ``(B, H/bh)`` over a ``(B, 8, H, Wd)``
stack of B independent lattices (parameter sweeps, many-user serving).
All lanes share one RNG stream -- the counters do not include the batch
index -- which keeps every lane bit-identical to the unbatched reference
and gives common-random-number coupling for paired ensemble comparisons;
diversity enters through the initial conditions and geometry.

Block decomposition (paper Figs. 7/8, adapted): the grid's second axis is
1-D over row bands of ``bh`` rows.  Each program reads its own band plus
the bands above and below (the same array bound three times with shifted
index maps -- the Pallas idiom for the paper's overlapping rectangles
A/B/C), computes the update for the interior band, and writes a disjoint
output band.  VMEM plays the role of the CUDA shared-memory apron C.
``steps <= bh`` keeps the T-row halo inside the neighbour bands.

2-D (x x y) blocking (``block_words`` = bw < Wd): wide shards (e.g.
``wdl=2048``) cannot hold a full row band plus temporaries in VMEM at deep
T, so the grid gains a third axis over word blocks -- ``(B, H/bh, Wd/bw)``
-- and each program owns a ``(bh, bw)`` tile.  The halo apron generalises
symmetrically: ``_shift_x``'s cross-word bit carry means each fused step
contaminates at most one word per side, so the tile reads a T-word apron
per x side (nine overlapping views of the array -- the 2-D version of the
paper's overlapping rectangles A/B/C) and each unrolled step consumes one
apron row per y side *and* one apron word per x side.  The in-tile
``_roll_x`` wrap is then garbage at the tile edges, but only in the
outermost word's edge bit, and that word is dropped the same step.
Periodic mode wraps the x index maps (mod ``Wd/bw``) so apron words are
the true periodic neighbours; extended mode clamps them (edge tiles
compute clamped garbage only in words the validity contract already
drops, exactly like the row case).  The RNG word coordinates reduce the
*global* word ``(xw0 + word) mod Wd_g`` per step, so redundant apron
compute stays bit-exact for free.  When ``bw == Wd`` the kernel keeps the
legacy single-view-per-row-band layout (no x apron, the rotate is the
periodic wrap); ``ops.py`` checks the VMEM budget either way and refuses
shapes that would not fit on a real v5e.

RNG in-kernel: collision chirality and forcing bits are counter-based
hashes of (row, word, t) -- recomputing them inside the kernel instead of
streaming precomputed random planes from HBM saves up to 2 more plane
reads per step (again: memory-bound, so this is a direct win).  Both modes
are supported for T=1 and bit-identical to ``ref.py``; T>1 requires
in-kernel RNG (precomputed planes for intermediate steps would defeat the
traffic win temporal blocking exists to deliver).  Row counters are
reduced mod the local lattice height, so halo rows past the periodic wrap
draw the owning row's stream exactly (this is what makes the redundant
apron compute of intermediate steps bit-exact).

Extended-shard mode (``global_mod``): under shard_map each device holds a
band of a larger lattice plus a depth-``d`` apron of exchanged neighbour
rows (and one halo word per x side) -- the time-extended version of the
paper's PThreads row bands.  The local array is then *not* periodic: the
y halo must come from the apron rows already present in the input, so the
band index maps clamp at the array edge instead of wrapping, and the
RNG / parity counters reduce the **global** coordinates
``(y0 + local_row) mod H_g`` and ``(xw0 + word) mod Wd_g`` (both global
extents threaded through the scalar block) so every apron row draws the
owning shard's stream bit-exactly.  Rows within T of the array edge (and
the low/high bits of the edge words) compute with clamped-garbage halos;
each launch therefore shrinks the valid region by T rows per side and one
lattice column per step, exactly the validity discipline of
``core/distributed.py``'s halo-widening.  When the launch has a single
row band per lane (``block_rows`` covers the padded height), each grid
step reads its whole lane before writing it, so the output may alias the
input plane stack (``input_output_aliases``) and the multi-launch carry
updates in place instead of double-buffering in HBM.  With multiple
bands, aliasing would be a program-order read-after-write hazard -- grid
step i reads band i-1, which step i-1 just wrote; only the VMEM prefetch
racing ahead of the writeback could save it, and that ordering is not
guaranteed on real hardware -- so multi-band launches never alias.

Static-geometry mode (``static_solid``): the solid plane is invariant
under the full update (streaming passes it through, collision's
bounce-back reads but never writes it), so for obstacle scenarios it is
dead weight in the output stream and -- sharded -- in every halo
exchange.  With ``static_solid`` the plane stack carries only the 7
*dynamic* planes (6 moving + rest) and the solid plane enters as a
separate read-only operand with its own three overlapping band views
(wrapping in periodic mode, clamped in extended mode, exactly like the
dynamic bands); each unrolled step slices the solid band to the current
working extent.  The kernel then writes 7 planes instead of 8 per launch
(~12.5% of the write traffic), and the sharded path exchanges 7 planes
per round while the pre-extended solid tile is cached per shard
(``core.distributed.make_solid_cache``) -- exchanged once per geometry,
not once per round.  All lanes of a batched launch share the one solid
operand (geometry is ensemble-invariant; diversity enters through the
initial conditions).

Rule plugins (``variant`` -> ``core.rulespec``): the kernel itself is
rule-agnostic.  ``variant`` names a registered :class:`RuleSpec`, and
everything FHP-specific above is really the spec's contract:

* ``spec.n_planes`` sizes the plane stack (8 for FHP, 2 for BML) and
  every VMEM/HBM model in ``ops.py``;
* ``spec.taps`` drive the streaming loop -- each tap is one
  ``(plane, ((dx_even, dy), (dx_odd, dy)))`` read with ``|dx|, |dy| <=
  1``, which is exactly the one-row/one-word-per-side-per-step budget
  the T-row/T-word halo aprons were sized for, so temporal and 2-D
  blocking work unchanged for every rule;
* ``spec.collide(streamed, words, t)`` is the pointwise boolean
  collision pass over the streamed taps; ``t`` is traced, so
  multi-sub-step rules (BML's alternating east/north moves) select on
  ``t % n_substeps`` inside one fused launch;
* ``spec.random_words`` is how many hashes the kernel draws per word
  and step (one per salt of ``prng.RANDOM_SALTS``; FHP-II 1, FHP-III 2):
  RNG-free rules skip the hash entirely (and accept any
  ``rng_in_kernel``), and a rule that draws one compiles to the one
  chirality hash;
* ``spec.solid_plane`` (must be the last plane) gates static-solid
  mode; ``spec.force`` gates the forcing pass.

Adding an automaton = registering a spec in ``core.rulespec``; the
cross-rule conformance harness (``tests/test_rule_conformance.py``)
then sweeps it against its byte oracle over T x block_words x
periodic/extended x batched with zero new kernel code.
"""
from __future__ import annotations

import functools
from typing import List

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from repro import telemetry
from repro.core import rulespec
from repro.core.prng import RANDOM_SALTS

WORD = 32
_U32 = jnp.uint32
_M1 = 0x85EBCA6B
_M2 = 0xC2B2AE35
_GOLD = 0x9E3779B9
BERNOULLI_BITS = 16


def _roll_x(p: jnp.ndarray, shift: int) -> jnp.ndarray:
    """Periodic word rotate along the last axis by +-1 (concat of slices --
    lowers to lane shifts on TPU, no gather)."""
    if shift == 1:
        return jnp.concatenate([p[..., -1:], p[..., :-1]], axis=-1)
    if shift == -1:
        return jnp.concatenate([p[..., 1:], p[..., :1]], axis=-1)
    return p


def _shift_x(p: jnp.ndarray, dx: int) -> jnp.ndarray:
    """Shift packed nodes by dx in x (periodic): bit shift + cross-word carry.

    Position x of the result holds the bit of source position x - dx, i.e.
    particles move *with* dx.  This is the 32-nodes-per-op primitive.
    """
    if dx == 0:
        return p
    if dx == 1:
        return (p << 1) | (_roll_x(p, 1) >> (WORD - 1))
    if dx == -1:
        return (p >> 1) | (_roll_x(p, -1) << (WORD - 1))
    raise ValueError(dx)


def _popcount_u32(v: jnp.ndarray) -> jnp.ndarray:
    """Per-word popcount via the SWAR reduction (shifts/masks/adds only,
    so it lowers on every Pallas backend; the final uint32 multiply
    wraps, which is exactly the horizontal byte-sum folding trick)."""
    v = v - ((v >> 1) & _U32(0x55555555))
    v = (v & _U32(0x33333333)) + ((v >> 2) & _U32(0x33333333))
    v = (v + (v >> 4)) & _U32(0x0F0F0F0F)
    return (v * _U32(0x01010101)) >> 24


def _block_moments(tile: jnp.ndarray, mask_words,
                   moment_terms, moment_coeffs) -> jnp.ndarray:
    """This block's moment partials: ``(n_moments,)`` int32.

    ``tile`` is the program's own valid ``(n_planes, bh, bw)`` interior
    at the recorded step; ``mask_words`` (or None) zeroes words outside
    the caller's validity bounds (extended mode: pad rows/words and the
    halo ring).  Each term is one plane's popcount (``(p,)``) or a
    pairwise-AND popcount (``(a, b)``); moments are their static int
    linear combinations (``core.rulespec.MomentSpec``) -- the cross-block
    (and cross-shard) sum epilogue lives in ``ops.py`` / ``distributed``.
    """
    sums = []
    for t in moment_terms:
        v = tile[t[0]]
        if len(t) == 2:
            v = v & tile[t[1]]
        if mask_words is not None:
            v = v & mask_words
        sums.append(jnp.sum(_popcount_u32(v).astype(jnp.int32)))
    out = []
    for row in moment_coeffs:
        acc = jnp.int32(0)
        for c, s in zip(row, sums):
            if c:
                acc = acc + jnp.int32(c) * s
        out.append(acc)
    return jnp.stack(out)


def _hash_u32(x: jnp.ndarray) -> jnp.ndarray:
    """murmur3 finalizer; bit-identical to ``core.prng.hash_u32``."""
    x = x ^ (x >> 16)
    x = x * _U32(_M1)
    x = x ^ (x >> 13)
    x = x * _U32(_M2)
    x = x ^ (x >> 16)
    return x


def _word_u32(rows: jnp.ndarray, cols: jnp.ndarray, t: jnp.ndarray,
              salt: int) -> jnp.ndarray:
    """In-kernel replica of ``core.prng.word_u32`` on 2-D iota counters."""
    ctr = rows * _U32(0x01000193) + cols
    salted = _U32((salt * _M2) & 0xFFFFFFFF)
    return _hash_u32(ctr ^ (t * _U32(_GOLD) + salted))


def _bernoulli_words(rows, cols, t, pq: int, salt: int) -> jnp.ndarray:
    """In-kernel replica of ``core.prng.bernoulli_words`` (MSB-first
    comparator against the binary expansion of the quantised p)."""
    shape = jnp.broadcast_shapes(rows.shape, cols.shape)
    if pq <= 0:
        return jnp.zeros(shape, dtype=_U32)
    if pq >= (1 << BERNOULLI_BITS):
        return jnp.full(shape, 0xFFFFFFFF, dtype=_U32)
    res = jnp.zeros(shape, dtype=_U32)
    eq = jnp.full(shape, 0xFFFFFFFF, dtype=_U32)
    last = (pq & -pq).bit_length() - 1
    for i in range(BERNOULLI_BITS - 1, last - 1, -1):
        r = _word_u32(rows, cols, t, salt=salt * 0x100 + i)
        if (pq >> i) & 1:
            res = res | (eq & ~r)
            eq = eq & r
        else:
            eq = eq & ~r
    return res


def _fused_step(cur: jnp.ndarray, rows_abs: jnp.ndarray, cols_abs, t,
                pq: int, rng_in_kernel: bool, spec,
                words_pre=(), acc_pre=None, solid=None,
                shrink_x: bool = False) -> jnp.ndarray:
    """One stream->collide(->force) update of an extended row stack.

    ``cur`` is ``(n_planes, n, w)`` -- or ``(n_planes - 1, n, w)``
    dynamic planes when the static ``solid`` interior ``(n-2, w or w-2)``
    is passed separately -- and the result keeps the plane count while
    shrinking to the interior ``n-2`` rows (each step consumes one apron
    row per side) and, with ``shrink_x`` (the 2-D blocked tile), the
    interior ``w-2`` words (each step also consumes one apron word per
    side, dropping the words whose ``_roll_x`` carry bit wrapped inside
    the tile).
    ``rows_abs`` is the ``(n, 1)`` int32 array of RNG/parity row
    coordinates of ``cur``'s rows, ``cols_abs`` the ``(1, w)`` int32
    array of RNG word coordinates (global offsets applied, periodic wrap
    already reduced).  ``spec`` is the ``core.rulespec.RuleSpec`` whose
    taps drive the streaming stencil and whose circuit collides.
    """
    n, w = cur.shape[1], cur.shape[2]
    xs = slice(1, w - 1) if shrink_x else slice(0, w)
    even = (rows_abs % 2) == 0

    # --- stream (paper's "motion", Listing 1), tap by tap -------------------
    streamed: List[jnp.ndarray] = []
    for tap in spec.taps:
        if solid is not None and tap.plane == spec.solid_plane:
            # geometry is static: read the read-only solid operand (already
            # sliced to the current interior) instead of the stack
            streamed.append(solid)
            continue
        src = cur[tap.plane]
        (dx0, dy), (dx1, _dy1) = tap.offsets
        if dx0 == dx1:
            moved = _shift_x(src, dx0)
        else:
            moved = jnp.where(even, _shift_x(src, dx0), _shift_x(src, dx1))
        # Destination-centric: interior row r (cur row r+1) receives from the
        # source cur row r + 1 - dy; parity above was that of the source row.
        streamed.append(moved[1 - dy:n - 1 - dy, xs])

    # --- collide (the rule's boolean circuit; FHP: LUT-equivalent algebra) --
    tt = jnp.asarray(t, _U32)
    words = words_pre
    if rng_in_kernel and (spec.random_words or pq > 0):
        rows_blk = rows_abs[1:n - 1].astype(_U32)
        cols_blk = cols_abs[:, xs].astype(_U32)
    if rng_in_kernel:
        words = tuple(_word_u32(rows_blk, cols_blk, tt, salt=salt)
                      for salt in RANDOM_SALTS[:spec.random_words])
    planes = spec.collide(streamed, words, t)

    # --- force (momentum injection with probability p) ----------------------
    if pq > 0:
        assert spec.force is not None, \
            f"rule {spec.name!r} has no force pass"
        if rng_in_kernel:
            acc = _bernoulli_words(rows_blk, cols_blk, tt, pq, salt=0x22)
        else:
            acc = acc_pre
        planes = spec.force(planes, acc)
    # static mode: the solid plane stays in its operand, not the stack
    return jnp.stack(planes[:spec.n_planes - 1] if solid is not None
                     else planes)


def fhp_kernel(s_ref, *rest,
               h: int, bh: int, wd: int, bw: int, pq: int, steps: int,
               rng_in_kernel: bool, variant: str = "fhp2",
               extended: bool = False, static_solid: bool = False,
               record_steps: tuple = (), moment_terms: tuple = (),
               moment_coeffs: tuple = (), moment_bounds=None,
               interpret: bool = False):
    """``steps`` fused FHP updates for a ``(bh, bw)`` tile.

    Refs (inputs first, output last, per pallas_call convention): the
    scalar block ``[t, y0, xw0, hg, wdg]`` (step counter + global
    coordinates of local element (0,0) + global lattice extents in rows /
    words -- traced, so the kernel composes with shard_map where the
    offsets are axis-index dependent), the overlapping views of the plane
    stack -- three row bands when x is un-blocked (``bw == wd``), nine
    ``(bh, bw)`` tiles (the 3x3 y-x neighbourhood, row-major) when x is
    blocked -- then, with ``static_solid``, the same number of views of
    the read-only solid plane, then -- when ``rng_in_kernel`` is False
    (T=1 only) -- the precomputed random words (one plane per
    ``spec.random_words``) and force plane for the tile,
    and finally the output tile.  Grid is ``(B, H/bh, Wd/bw)``: axis 0 is
    the ensemble lane, axis 1 the row band, axis 2 the word block.

    ``extended`` selects the non-wrapping shard mode: RNG / parity rows
    reduce the *global* row ``(y0 + local) mod hg`` and words reduce
    ``(xw0 + word) mod wdg``, so apron rows (including those past the
    global periodic wrap, e.g. shard 0's top halo) reproduce the owning
    shard's stream; the periodic-mode local reduction ``y0 + local mod h``
    cannot express that.

    ``static_solid`` selects the dynamic-plane layout (module
    docstring): the plane refs carry every plane but the rule's solid
    plane; the solid band is assembled from its own views once and
    sliced per unrolled step.

    Fused observables (``record_steps`` non-empty): after unrolled step
    ``s`` in ``record_steps`` the program popcount-reduces its own
    ``(bh, bw)`` interior of the working stack -- which is fully valid at
    every intermediate step, because the apron only shields halo cells --
    into the static ``MomentSpec`` linear combinations
    (``moment_terms`` / ``moment_coeffs``), and a second output block
    ``(len(record_steps), n_moments)`` int32 carries the per-block
    partials out (the cross-block sum is ``ops.py``'s epilogue; Pallas
    revisiting semantics make in-kernel cross-block accumulation
    non-portable).  ``moment_bounds = (r0, r1, c0, c1)`` masks the
    reduction to array-local rows ``[r0, r1)`` x words ``[c0, c1)`` --
    extended mode's validity window, which also drops the row/word
    padding ``ops.run_extended`` appends.

    ``interpret`` (the CPU's interpret mode) ends each unrolled step in
    an optimization barrier: XLA's CPU compiler would otherwise fuse
    each step's circuit into every consumer in the next step, and the
    compile grows steeply with T (FHP-III at T=4: ~295 s against ~6 s).
    The chip's program has no barrier.
    """
    spec = rulespec.get_rule(variant)
    x_blocked = bw < wd
    nv = 9 if x_blocked else 3
    plane_refs = rest[:nv]
    rest = rest[nv:]
    if static_solid:
        sol_refs, rest = rest[:nv], rest[nv:]
    if record_steps:
        mom_ref = rest[-1]
        rest = rest[:-1]
    extra_refs = rest[:-1]
    out_ref = rest[-1]
    i = pl.program_id(1)
    j = pl.program_id(2)
    t0 = s_ref[0, 0]
    y0 = s_ref[0, 1]
    xw0 = s_ref[0, 2]
    T = steps
    hx = T if x_blocked else 0                 # x apron width in words

    # Overlapping read: T halo rows above = tail of the upper band, T halo
    # rows below = head of the lower band; with x blocking also T halo
    # words from the left/right (and corner) tiles.  In periodic mode the
    # index maps wrap, so the global wraps match the jnp.roll reference
    # exactly; in extended mode they clamp (the halo is apron data already
    # inside the array, and edge tiles compute garbage only in rows/words
    # the validity contract drops).
    ysl = ((0, slice(bh - T, bh)), (1, slice(None)), (2, slice(0, T)))
    if x_blocked:
        xsl = ((0, slice(bw - T, bw)), (1, slice(None)), (2, slice(0, T)))

        def assemble(refs, lead):
            cols = []
            for xi, xcut in xsl:
                parts = [(refs[yi * 3 + xi][0] if lead
                          else refs[yi * 3 + xi][...])[..., ycut, xcut]
                         for yi, ycut in ysl]
                cols.append(jnp.concatenate(parts, axis=-2))
            return jnp.concatenate(cols, axis=-1)
    else:
        def assemble(refs, lead):
            parts = [(refs[yi][0] if lead else refs[yi][...])[..., ycut, :]
                     for yi, ycut in ysl]
            return jnp.concatenate(parts, axis=-2)

    cur = assemble(plane_refs, lead=True)
    if record_steps:
        # The (bh, bw) interior always covers array rows i*bh + [0, bh)
        # and words j*bw + [0, bw); the validity mask is therefore one
        # word mask shared by every recorded step.
        mask_words = None
        if moment_bounds is not None:
            r0, r1, c0, c1 = moment_bounds
            ri = i * bh + jax.lax.broadcasted_iota(jnp.int32, (bh, bw), 0)
            ci = j * bw + jax.lax.broadcasted_iota(jnp.int32, (bh, bw), 1)
            mask_words = jnp.where(
                (ri >= r0) & (ri < r1) & (ci >= c0) & (ci < c1),
                _U32(0xFFFFFFFF), _U32(0))
        records = []
    if static_solid:
        # Solid extent matching cur's initial (bh + 2T, bw + 2*hx) tile;
        # step s works on tile rows [s, n0 - s) and words [s, w0 - s), so
        # its interior is band[s+1:n0-s-1, s+1:w0-s-1] (x only if blocked).
        solid_band = assemble(sol_refs, lead=False)

    for s in range(T):
        n = cur.shape[1]                      # bh + 2 * (T - s)
        w = cur.shape[2]                      # bw + 2 * (hx - s*x_blocked)
        # Local row of cur row r is  i*bh - (T - s) + r  (and word c is
        # j*bw - (hx - s) + c when x is blocked).  Periodic mode reduces
        # them mod the *local* lattice extents so coordinates past the
        # local wrap hash (and stream with the parity of) the owning
        # cell's coordinates; extended mode reduces the *global*
        # coordinates mod (H_g, Wd_g) so apron cells across the global
        # wrap draw the owning shard's stream -- required for the
        # intermediate-step apron compute to be bit-exact.
        row_iota = jax.lax.broadcasted_iota(jnp.int32, (n, 1), 0)
        col_iota = jax.lax.broadcasted_iota(jnp.int32, (1, w), 1)
        xoff = j * bw - (hx - s if x_blocked else 0)
        if extended:
            rows_abs = (y0 + i * bh - (T - s) + row_iota) % s_ref[0, 3]
            cols_abs = (xw0 + xoff + col_iota) % s_ref[0, 4]
        else:
            rows_abs = y0 + (i * bh - (T - s) + row_iota) % h
            cols_abs = xw0 + (xoff + col_iota) % wd
        if static_solid:
            sol = solid_band[s + 1:s + n - 1,
                             s + 1:s + w - 1] if x_blocked else \
                  solid_band[s + 1:s + n - 1]
        else:
            sol = None
        if rng_in_kernel or not spec.random_words:
            cur = _fused_step(cur, rows_abs, cols_abs, t0 + s, pq,
                              rng_in_kernel, spec, solid=sol,
                              shrink_x=x_blocked)
        else:
            nw = spec.random_words
            cur = _fused_step(cur, rows_abs, cols_abs, t0 + s, pq, False,
                              spec,
                              words_pre=tuple(r[...] for r in extra_refs[:nw]),
                              acc_pre=extra_refs[nw][...] if pq > 0 else None,
                              solid=sol, shrink_x=x_blocked)
        if interpret:
            cur = jax.lax.optimization_barrier(cur)
        if record_steps and s in record_steps:
            oy = (cur.shape[1] - bh) // 2
            ox = (cur.shape[2] - bw) // 2
            tile = cur[:, oy:oy + bh, ox:ox + bw]
            records.append(_block_moments(tile, mask_words,
                                          moment_terms, moment_coeffs))

    out_ref[0] = cur
    if record_steps:
        mom_ref[0, 0, 0] = jnp.stack(records)


def make_fhp_step(h: int, wd: int, *, bh: int, pq: int,
                  rng_in_kernel: bool, interpret: bool,
                  variant: str = "fhp2", steps: int = 1, batch: int = 1,
                  extended: bool = False, donate: bool = False,
                  static_solid: bool = False, bw: int = 0,
                  record_steps: tuple = (), moment_terms: tuple = (),
                  moment_coeffs: tuple = (), moment_bounds=None):
    """Build the pallas_call for a (B, 8, h, wd) plane stack -- or, with
    ``static_solid``, a (B, 7, h, wd) dynamic stack plus a read-only
    (h, wd) solid plane operand (module docstring).

    ``bw`` (block_words, 0 = full width) switches on 2-D (x x y) blocking:
    the grid gains a word-block axis and every view becomes a (bh, bw)
    tile with a T-word x apron (module docstring).  ``extended`` builds
    the non-wrapping shard-mode kernel (clamped band maps +
    global-coordinate RNG; see module docstring).  ``donate`` aliases the
    plane-stack input to the output (no HBM double-buffer); only legal in
    extended mode with a single tile per lane (``bh == h`` and ``bw ==
    wd``), where every grid step reads its whole lane before writing --
    multi-tile grids would read tile i-1 after step i-1's writeback (see
    module docstring).

    ``record_steps`` (sorted tuple of in-launch step indices) switches on
    the fused-observables output: the call returns ``(planes, partials)``
    where ``partials`` is ``(batch, H/bh, Wd/bw, len(record_steps),
    n_moments)`` int32 per-block moment partials (``fhp_kernel``
    docstring); callers sum over the block axes.

    With the default ``telemetry`` on, each build records the event
    ``kernel.build`` (``rule``, ``random_words``, ``ops_per_word_step``
    from ``rulespec.ops_per_word_step`` at this ``pq``, and the tile
    ``block_rows``, ``block_words``, ``steps_per_launch``).  The build
    runs where the caller is traced, on the host; with telemetry off
    nothing is counted or recorded.
    """
    spec = rulespec.get_rule(variant)
    bw = bw or wd
    x_blocked = bw < wd
    assert h % bh == 0, f"H={h} must be a multiple of block_rows={bh}"
    assert wd % bw == 0, f"Wd={wd} must be a multiple of block_words={bw}"
    assert 1 <= steps <= bh, \
        f"steps_per_launch={steps} needs a {steps}-row halo <= block_rows={bh}"
    assert not x_blocked or steps <= bw, \
        f"steps_per_launch={steps} needs a {steps}-word x apron <= " \
        f"block_words={bw}"
    assert rng_in_kernel or steps == 1, \
        "precomputed RNG planes only cover one step: steps_per_launch == 1"
    assert not donate or (extended and bh == h and bw == wd), \
        "input_output_aliases needs extended mode and a single tile " \
        "(multi-tile in-place update is a read-after-write hazard)"
    assert rng_in_kernel or not static_solid, \
        "static_solid is a fused-path feature: rng_in_kernel=True"
    assert not static_solid or spec.solid_plane is not None, \
        f"rule {variant!r} has no solid plane: static_solid unsupported"
    nb = h // bh
    nbx = wd // bw
    np_ = spec.n_planes - 1 if static_solid else spec.n_planes

    def yidx(dy):
        if dy == 0:
            return lambda i: i
        if extended:                              # clamp at the array edge
            return (lambda i: jnp.maximum(i - 1, 0)) if dy < 0 else \
                   (lambda i: jnp.minimum(i + 1, nb - 1))
        return (lambda i: (i + nb - 1) % nb) if dy < 0 else \
               (lambda i: (i + 1) % nb)

    def xidx(dx):
        if dx == 0:
            return lambda j: j
        if extended:
            return (lambda j: jnp.maximum(j - 1, 0)) if dx < 0 else \
                   (lambda j: jnp.minimum(j + 1, nbx - 1))
        return (lambda j: (j + nbx - 1) % nbx) if dx < 0 else \
               (lambda j: (j + 1) % nbx)

    # The overlapping-view neighbourhood, row-major over (dy, dx): three
    # row bands when x is un-blocked, the full 3x3 tile neighbourhood
    # (corners included -- diagonal streaming crosses them) when blocked.
    hood = [(dy, dx) for dy in (-1, 0, 1)
            for dx in ((-1, 0, 1) if x_blocked else (0,))]
    band = lambda fy, fx: pl.BlockSpec(
        (1, np_, bh, bw), lambda b, i, j, fy=fy, fx=fx: (b, 0, fy(i), fx(j)))
    in_specs = [
        pl.BlockSpec((1, 5), lambda b, i, j: (0, 0)),  # [t, y0, xw0, hg, wdg]
    ]
    in_specs += [band(yidx(dy), xidx(dx)) for dy, dx in hood]
    if static_solid:
        # The solid plane's own overlapping views; shared by every
        # ensemble lane (the index map ignores b).
        sband = lambda fy, fx: pl.BlockSpec(
            (bh, bw), lambda b, i, j, fy=fy, fx=fx: (fy(i), fx(j)))
        in_specs += [sband(yidx(dy), xidx(dx)) for dy, dx in hood]
    if not rng_in_kernel and spec.random_words:
        in_specs += [pl.BlockSpec((bh, bw), lambda b, i, j: (i, j))   # words
                     for _ in range(spec.random_words)]
        if pq > 0:
            in_specs.append(
                pl.BlockSpec((bh, bw), lambda b, i, j: (i, j)))        # accel

    record_steps = tuple(sorted(record_steps))
    assert all(0 <= s < steps for s in record_steps), (record_steps, steps)
    tel = telemetry.default()
    if tel.enabled:
        tel.event("kernel.build", rule=variant,
                  random_words=spec.random_words,
                  ops_per_word_step=rulespec.ops_per_word_step(
                      spec, pq / (1 << BERNOULLI_BITS)),
                  block_rows=bh, block_words=bw, steps_per_launch=steps)
    kern = functools.partial(fhp_kernel, h=h, bh=bh, wd=wd, bw=bw, pq=pq,
                             steps=steps, rng_in_kernel=rng_in_kernel,
                             variant=variant, extended=extended,
                             static_solid=static_solid,
                             record_steps=record_steps,
                             moment_terms=moment_terms,
                             moment_coeffs=moment_coeffs,
                             moment_bounds=moment_bounds,
                             interpret=interpret)
    out_specs = pl.BlockSpec((1, np_, bh, bw), lambda b, i, j: (b, 0, i, j))
    out_shape = jax.ShapeDtypeStruct((batch, np_, h, wd), jnp.uint32)
    if record_steps:
        nr, nm = len(record_steps), len(moment_coeffs)
        out_specs = [out_specs, pl.BlockSpec(
            (1, 1, 1, nr, nm), lambda b, i, j: (b, i, j, 0, 0))]
        out_shape = [out_shape, jax.ShapeDtypeStruct(
            (batch, nb, nbx, nr, nm), jnp.int32)]
    return pl.pallas_call(
        kern,
        grid=(batch, nb, nbx),
        in_specs=in_specs,
        out_specs=out_specs,
        out_shape=out_shape,
        input_output_aliases={1: 0} if donate else {},
        interpret=interpret,
    )
