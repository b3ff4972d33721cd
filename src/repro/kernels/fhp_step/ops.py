"""Jitted wrappers for the fused, temporally-blocked FHP Pallas kernel.

``fhp_step_pallas`` is a drop-in replacement for
``core.bitplane.step_planes`` (bit-identical given the same
``t / p_force / y0 / xw0``) that also accepts a leading ensemble batch
axis and ``steps_per_launch`` = T fused steps per kernel launch;
``run_pallas`` advances many steps with a donated carry, launching the
multi-step kernel ``steps // T`` times (plus one ``steps % T``-step
remainder launch).  ``run_extended`` is the shard-map hot path: it
advances a halo-extended shard array ``depth`` steps in ceil(depth/T)
donated launches with **global**-coordinate RNG (mod ``hg``/``wdg``), so
one depth-``d`` exchange feeds ``d`` in-kernel steps.
``run_extended_split`` is the compute/communication-overlap variant: it
advances the same extended shard as an **interior** launch (bare shard,
no apron dependence) plus four thin **boundary** launches (top/bottom
row bands, left/right word strips) whose light cones are the only ones
that touch the exchanged halo, then composes the exact valid pieces --
bit-identical to ``run_extended`` by construction.  ``autotune_launch``
picks the 2-D tile ``(block_rows, block_words, steps_per_launch)`` -- or,
given ``max_depth``, the joint ``(block_rows, block_words,
steps_per_launch, depth, overlap)`` for the sharded path including the
exchange bandwidth + latency terms -- under the VMEM budget from a
bytes-per-site-update model; ``block_words`` below the width selects the
x-blocked kernel grid that lifts the VMEM ceiling on wide shards.  On
non-TPU backends the kernel runs in interpret mode.
"""
from __future__ import annotations

import functools
from typing import Tuple

import jax
import jax.numpy as jnp

from repro.core import prng
from repro.kernels.fhp_step import kernel as _k
from repro.roofline import analysis as _roofline
from repro import telemetry

# v5e VMEM is ~128 MiB but a realistic per-kernel working-set budget is far
# smaller; we keep the resident blocks (3 input bands + 1 output band +
# boolean temporaries, ~2x slack) under this.
VMEM_BUDGET_BYTES = 8 * 2 ** 20

# Compute cost of updating one extended row relative to moving one row
# across HBM: the kernel is memory-bound (paper sec. 4; roofline/analysis),
# so redundant apron rows are cheap but not free.  Used by the autotuner.
COMPUTE_ROW_WEIGHT = 0.2

MAX_STEPS_PER_LAUNCH = 8

# The (sublane, lane) tiling of a 32-bit VMEM block on TPU: Mosaic only
# compiles BlockSpecs whose last two dims are multiples of these or equal
# to the array's own extents (``tile_error``).
SUBLANES = 8
LANES = 128


def _pow2_ge(x: int) -> int:
    """Smallest power of two >= x (>= 1)."""
    p = 1
    while p < x:
        p *= 2
    return p


def vmem_bytes(bh: int, wd: int, steps: int = 1, block_words: int = 0,
               static_solid: bool = False, n_planes: int = 8,
               moments_words: int = 0) -> int:
    """Estimated VMEM working set of one program instance.

    Resident input views + 1 output tile (3 + 1 row bands when x is
    un-blocked; 9 + 1 ``(bh, bw)`` tiles for the 2-D blocked grid), plus
    the unrolled working stack and boolean temporaries on the widest
    (first-step) extent of ``bh + 2*steps`` rows (x ``bw + 2*steps``
    words when x is blocked).  ``static_solid`` adds the read-only
    pre-extended solid operand: its own resident views plus the assembled
    solid band -- without it the autotuner could admit a tile that
    overflows the budget on the 7-plane static path.  ``n_planes`` is the
    rule's plane count (``core.rulespec``): fewer planes per node mean a
    proportionally smaller working set, so e.g. 2-plane BML admits far
    taller bands than 8-plane FHP.  ``moments_words`` (= records x
    n_moments) prices the fused-observables output block plus one
    popcount temporary per recorded step.
    """
    bw = min(block_words, wd) if block_words else wd
    x_blocked = bw < wd
    np_ = n_planes - 1 if static_solid else n_planes
    views = 9 if x_blocked else 3
    ew = bw + 2 * steps if x_blocked else bw
    band = np_ * bh * bw * 4
    ext = np_ * (bh + 2 * steps) * ew * 4     # current plane stack
    # collision conditions + streams scale with the plane count (~3x)
    temps = 3 * n_planes * (bh + 2 * steps) * ew * 4
    total = (views + 1) * band + ext + temps
    if static_solid:
        total += views * bh * bw * 4 + (bh + 2 * steps) * ew * 4
    if moments_words:
        total += 4 * moments_words + bh * bw * 4  # out block + popcount temp
    return total


def tile_error(bh: int, bw: int, h: int, wd: int) -> str | None:
    """Why Mosaic would refuse a ``(bh, bw)`` tile of an ``(h, wd)``
    array, or None when it compiles: the last two dims of every
    BlockSpec must be multiples of the (8, 128) sublane x lane tiling or
    equal to the array's own extents."""
    if bh % SUBLANES and bh != h:
        return (f"block_rows={bh} is neither a multiple of {SUBLANES} nor "
                f"the full height {h}")
    if bw % LANES and bw != wd:
        return (f"block_words={bw} is neither a multiple of {LANES} nor "
                f"the full width {wd}")
    return None


def _bh_candidates(h: int | None):
    """Band heights the chip compiles, tallest first: the powers of two
    8..32 (dividing ``h`` when it is given), else the full extent."""
    cands = [bh for bh in (32, 16, SUBLANES) if h is None or h % bh == 0]
    return cands or [h]


def _bw_candidates(width: int, divisors_only: bool):
    """Word-block candidates for the joint tile search: the full width
    (legacy 1-D row bands) plus descending powers of two from ``LANES``
    up.  The periodic path needs ``bw | width``; the extended path pads,
    so any bw goes."""
    cands = [width]
    bw = LANES
    while bw * 2 < width:
        bw *= 2
    while LANES <= bw < width:
        if not divisors_only or width % bw == 0:
            cands.append(bw)
        bw //= 2
    return cands


def _pick_bh(wd: int, steps: int, h: int | None, block_words: int = 0,
             static_solid: bool = False, n_planes: int = 8) -> int:
    """Tallest band height from ``_bh_candidates`` that admits the
    ``steps``-row halo, fits VMEM, and (when ``h`` is given) divides H."""
    for bh in _bh_candidates(h):
        if bh >= steps and vmem_bytes(bh, wd, steps, block_words,
                                      static_solid, n_planes
                                      ) <= VMEM_BUDGET_BYTES:
            return bh
    raise ValueError(f"no valid block for H={h}, Wd={wd}, "
                     f"block_words={block_words}, "
                     f"steps_per_launch={steps}")


def pick_block_rows(h: int, wd: int, steps: int = 1,
                    n_planes: int = 8) -> int:
    """Tallest band height (8, 16 or 32 dividing H, else H) that admits
    the ``steps``-row halo and fits VMEM."""
    return _pick_bh(wd, steps, h, n_planes=n_planes)


def pick_block_rows_extended(wd: int, steps: int = 1,
                             n_planes: int = 8) -> int:
    """``pick_block_rows`` without the divisibility constraint: the
    extended-shard path row-pads the array to a block multiple (pad rows
    sit past the validity region)."""
    return _pick_bh(wd, steps, None, n_planes=n_planes)


def pick_tile_extended(wd: int, steps: int = 1,
                       static_solid: bool = False,
                       n_planes: int = 8) -> Tuple[int, int]:
    """``(block_rows, block_words)`` for the extended path: the legacy
    full-width 1-D band when it fits VMEM, else the widest word block
    from ``_bw_candidates`` that fits (the extended path word-pads the
    array to a block multiple, so ``bw`` need not divide the width)."""
    for bw in _bw_candidates(wd, divisors_only=False):
        try:
            return _pick_bh(wd, steps, None, block_words=bw,
                            static_solid=static_solid,
                            n_planes=n_planes), bw
        except ValueError:
            continue
    raise ValueError(f"no valid 2-D tile for Wd={wd}, "
                     f"steps_per_launch={steps}")


def launch_cost(bh: int, steps: int, block_words: int = 0,
                width_words: int = 0, moments_words: int = 0) -> float:
    """Modeled cost per useful site update, in HBM word-cell units.

    Per program per launch: a ``(bh + 2*steps) x (bw + 2*hx)`` tile read
    + a ``bh x bw`` tile written (``hx`` = ``steps`` when x is blocked,
    else 0 -- the x-apron redundancy term), plus the shrinking apron
    extents of (cheap, weighted) redundant compute, for ``bh * bw *
    steps`` useful word-updates.  With ``block_words`` unset (or >= the
    width) this reduces exactly to the legacy 1-D row-unit model.
    ``moments_words`` (records x n_moments) adds the fused-observables
    partial block each program writes -- tiny next to the plane stack,
    which is exactly why in-kernel recording beats a post-hoc re-stream.
    """
    bw = (min(block_words, width_words) if block_words and width_words
          else block_words) or width_words or 1
    x_blocked = bool(block_words and width_words and
                     block_words < width_words)
    hx = steps if x_blocked else 0
    mem = (bh + 2 * steps) * (bw + 2 * hx) + bh * bw + moments_words
    comp = sum((bh + 2 * (steps - s - 1))
               * (bw + 2 * (steps - s - 1) if x_blocked else bw)
               for s in range(steps))
    return (mem + COMPUTE_ROW_WEIGHT * comp) / (bh * bw * steps)


def hbm_bytes_per_site(bh: int, steps: int, block_words: int = 0,
                       width_words: int = 0, n_planes: int = 8,
                       moments_words: int = 0) -> float:
    """Modeled HBM traffic per site update for the fused T-step kernel.
    ``n_planes`` scales the per-word byte cost (per-rule plane count);
    ``moments_words`` adds the per-block fused-observables write."""
    bw = (min(block_words, width_words) if block_words and width_words
          else block_words) or width_words or 1
    x_blocked = bool(block_words and width_words and
                     block_words < width_words)
    hx = steps if x_blocked else 0
    return ((n_planes * 4 * ((bh + 2 * steps) * (bw + 2 * hx) + bh * bw)
             + 4 * moments_words)
            / (32.0 * bh * bw * steps))


def sharded_hbm_bytes_per_site(bh: int, steps: int, depth: int,
                               hl: int, wdl: int,
                               static_solid: bool = False,
                               block_words: int = 0,
                               n_planes: int = 8) -> float:
    """Modeled HBM traffic per useful site update of the sharded
    extended-shard path (``roofline.analysis.sharded_fhp_traffic``)."""
    return _roofline.sharded_fhp_traffic(
        hl, wdl, depth=depth, T=steps, block_rows=bh,
        block_words=block_words, n_planes=n_planes,
        static_solid=static_solid)["hbm_bytes_per_site_step"]


def sharded_launch_cost(bh: int, steps: int, depth: int,
                        hl: int, wdl: int, *,
                        static_solid: bool = False,
                        block_words: int = 0,
                        n_planes: int = 8,
                        overlap: bool = False,
                        exchange_latency_s: float | None = None) -> float:
    """Modeled seconds per useful site update for the sharded path: HBM +
    weighted apron compute (incl. the x-apron redundancy of a 2-D tile) +
    exchange bandwidth + exchange latency.  ``overlap=True`` prices the
    interior/boundary split of ``run_extended_split``: the exchange hides
    under the interior launch, so the round costs ``max(t_exchange,
    t_interior) + t_boundary`` instead of the serial sum (degenerate
    shards price at the serial cost, like the runtime fallback).

    ``exchange_latency_s=None`` uses the measured ppermute round-trip
    latency when a real multi-chip mesh is attached, else the 3 us
    constant (``roofline.analysis.measured_exchange_latency``)."""
    if exchange_latency_s is None:
        exchange_latency_s = _roofline.measured_exchange_latency()
    return _roofline.sharded_fhp_traffic(
        hl, wdl, depth=depth, T=steps, block_rows=bh,
        block_words=block_words, n_planes=n_planes,
        compute_row_weight=COMPUTE_ROW_WEIGHT,
        exchange_latency_s=exchange_latency_s,
        static_solid=static_solid, overlap=overlap)["total_s_per_site"]


def autotune_launch(h: int, wd: int, *, max_steps: int = MAX_STEPS_PER_LAUNCH,
                    vmem_budget: int = VMEM_BUDGET_BYTES,
                    max_depth: int | None = None,
                    static_solid: bool = False,
                    n_planes: int = 8,
                    exchange_latency_s: float | None = None,
                    moments_words: int = 0):
    """Choose the launch configuration minimizing modeled cost under the
    VMEM budget -- the joint 2-D tile search.

    Single-device (``max_depth=None``): returns ``(block_rows,
    block_words, steps_per_launch)`` minimizing ``launch_cost`` subject
    to divisibility (both axes) and halo depth <= block extents.
    ``block_words == wd`` is the legacy 1-D row-band kernel; a narrower
    tile pays the x-apron redundancy term, so 2-D wins exactly when the
    VMEM ceiling bars the 1-D band from a deeper T.

    Sharded (``max_depth`` set): ``h``/``wd`` are the per-shard ``hl`` /
    ``wdl``; returns the joint ``(block_rows, block_words,
    steps_per_launch, depth, overlap)`` minimizing ``sharded_launch_cost``
    -- HBM traffic of the extended array plus the exchange bandwidth and
    per-exchange latency terms, so deeper halos win exactly until apron
    redundancy outgrows the amortised exchange cost.  ``overlap`` (bool)
    selects the interior/boundary split of ``run_extended_split``, which
    hides the exchange under the interior launch at the price of the
    split's extra per-slice aprons -- overlap shifts the optimal depth
    because the exchange is then partially free, hence the joint search.
    Ties prefer ``overlap=False`` (the serial path is the simpler plan).
    Every tile searched is one the chip compiles (``tile_error``):
    ``block_rows`` from ``_bh_candidates``, ``block_words`` from
    ``_bw_candidates``.
    The extended path has no divisibility constraint (rows and words are
    padded), but the T-row/T-word halo must fit the tile and the depth
    must fit the one-word x halo (depth <= 31).  ``block_words`` here is
    a tile of the *extended* width ``wdl + 2``.

    ``static_solid`` prices the dynamic-plane schedule (cached solid
    apron + read-only solid operand in the VMEM model); ``n_planes`` is
    the rule's plane count (``core.rulespec``) -- it scales both the
    VMEM working set and the modeled HBM/ICI bytes, so low-plane rules
    (BML) admit taller tiles at the same budget.
    ``exchange_latency_s=None`` resolves to the measured ppermute latency
    (constant fallback off-mesh) -- only for the sharded search, whose
    cost model is the only consumer.
    ``moments_words`` (records x n_moments of the fused-observables
    output, 0 = off) prices the extra per-block partial write in both
    the VMEM check and the launch cost, so dense recording can tip the
    tuner toward a launch schedule with fewer, larger blocks.
    """
    best = None
    best_cost = None
    if max_depth is None:
        for bw in _bw_candidates(wd, divisors_only=True):
            x_blocked = bw < wd
            for bh in _bh_candidates(h):
                t_cap = min(bh, max_steps, bw if x_blocked else bh)
                for steps in range(1, t_cap + 1):
                    if vmem_bytes(bh, wd, steps, bw, n_planes=n_planes,
                                  moments_words=moments_words
                                  ) > vmem_budget:
                        break
                    cost = launch_cost(bh, steps, bw, wd,
                                       moments_words=moments_words)
                    if best_cost is None or cost < best_cost:
                        best, best_cost = (bh, bw, steps), cost
        if best is None:
            raise ValueError(f"no valid launch config for H={h}, Wd={wd}")
        return best

    if exchange_latency_s is None:
        exchange_latency_s = _roofline.measured_exchange_latency()
    hl, wdl = h, wd
    we = wdl + 2                           # extended shard width in words
    for bw in _bw_candidates(we, divisors_only=False):
        x_blocked = bw < we
        for bh in _bh_candidates(None):
            # depth <= hl: the nearest-neighbour exchange cannot source a
            # deeper apron than one shard's rows (distributed.py asserts).
            for depth in range(1, min(max_depth, 31, hl) + 1):
                t_cap = min(bh, max_steps, depth,
                            bw if x_blocked else bh)
                for steps in range(1, t_cap + 1):
                    if vmem_bytes(bh, we, steps, bw, static_solid,
                                  n_planes, moments_words=moments_words
                                  ) > vmem_budget:
                        break
                    # The split's boundary launches cap the tile to each
                    # slice's (smaller) footprint, so the serial VMEM
                    # check above covers overlap=True as well.
                    for overlap in (False, True):
                        cost = sharded_launch_cost(
                            bh, steps, depth, hl, wdl,
                            static_solid=static_solid, block_words=bw,
                            n_planes=n_planes, overlap=overlap,
                            exchange_latency_s=exchange_latency_s)
                        if best_cost is None or cost < best_cost:
                            best, best_cost = (bh, bw, steps, depth,
                                               overlap), cost
    if best is None:
        raise ValueError(f"no valid sharded launch config for "
                         f"hl={hl}, wdl={wdl}")
    return best


@functools.partial(jax.jit, static_argnames=(
    "p_force", "block_rows", "block_words", "rng_in_kernel", "interpret",
    "variant", "steps_per_launch", "extended", "hg", "wdg", "donate",
    "record_steps", "moment_bounds"))
def fhp_step_pallas(planes: jnp.ndarray, t, *, p_force: float = 0.0,
                    y0=0, xw0=0, block_rows: int = 0, block_words: int = 0,
                    rng_in_kernel: bool = True,
                    interpret: bool | None = None,
                    variant: str = "fhp2",
                    steps_per_launch: int = 1,
                    extended: bool = False,
                    hg: int | None = None, wdg: int | None = None,
                    donate: bool = False,
                    solid: jnp.ndarray | None = None,
                    record_steps: tuple = (),
                    moment_bounds: tuple | None = None) -> jnp.ndarray:
    """``steps_per_launch`` fused stream+collide(+force) FHP steps in one
    kernel launch, on ``(8, H, Wd)`` or batched ``(B, 8, H, Wd)`` uint32
    planes (ensemble lanes; all lanes share the RNG stream).

    ``y0``/``xw0`` (global coordinates of local element (0,0)) may be
    traced -- they ride into the kernel in the scalar block, so the kernel
    composes with shard_map (per-shard offsets from axis_index).

    ``extended`` runs the non-wrapping shard mode on a halo-extended
    array: ``hg``/``wdg`` are the **global** lattice extents (rows /
    packed words) the RNG and parity counters reduce mod, so apron rows
    and halo words -- including those across the global periodic wrap --
    draw the owning shard's stream bit-exactly.  Each extended launch
    shrinks the valid region by ``steps_per_launch`` rows per side and
    one lattice column per step.  ``donate`` aliases the plane input to
    the output (extended mode only).

    ``solid`` switches on static-geometry mode: ``planes`` then carries
    the 7 *dynamic* planes only and the (H, Wd) solid plane rides as a
    read-only operand shared by all lanes -- the kernel writes 7 planes
    per launch instead of 8 (see ``kernel.py``).

    ``block_words`` (0 = full width) selects the 2-D (x x y) blocked grid:
    each program owns a ``(block_rows, block_words)`` tile with a
    ``steps_per_launch``-word x apron; ``block_words`` must divide ``Wd``
    (``run_extended`` word-pads before calling).

    ``record_steps`` (tuple of in-launch step indices) turns on fused
    observables: the rule's ``MomentSpec`` popcount reductions are
    accumulated in-kernel while the planes sit in VMEM and the call
    returns ``(planes, moments)`` with ``moments`` a ``(B?,
    len(record_steps), n_moments)`` int32 time series (cross-block sum
    applied here -- the kernel writes per-block partials).
    ``moment_bounds = (r0, r1, c0, c1)`` restricts the reduction to
    array rows ``[r0, r1)`` x words ``[c0, c1)`` (the extended-shard
    validity window); ``None`` reduces the whole (periodic) lattice."""
    from repro.core import rulespec
    spec = rulespec.get_rule(variant)
    squeeze = planes.ndim == 3
    if squeeze:
        planes = planes[None]
    b, np_, h, wd = planes.shape
    static_solid = solid is not None
    want = spec.n_planes - 1 if static_solid else spec.n_planes
    if np_ != want:
        raise ValueError(
            f"plane stack has {np_} planes; rule {variant!r} expects "
            f"{want}{' dynamic (solid passed separately)' if static_solid else ''}")
    if static_solid and spec.solid_plane is None:
        raise ValueError(f"rule {variant!r} has no solid plane")
    if static_solid and solid.shape != (h, wd):
        raise ValueError(f"solid plane {solid.shape} != lattice {(h, wd)}")
    if p_force > 0 and spec.force is None:
        raise ValueError(f"rule {variant!r} has no force pass: p_force=0")
    T = steps_per_launch
    if T != 1 and not rng_in_kernel:
        raise ValueError("steps_per_launch > 1 requires rng_in_kernel=True "
                         "(precomputed RNG planes cover a single step)")
    if static_solid and not rng_in_kernel:
        raise ValueError("static-solid mode is a fused-path feature "
                         "(rng_in_kernel=True)")
    if extended:
        if not rng_in_kernel:
            raise ValueError("extended mode draws global-coordinate RNG "
                             "in-kernel (rng_in_kernel=True)")
        if hg is None or wdg is None:
            raise ValueError("extended mode needs the global extents hg/wdg")
    elif donate:
        raise ValueError("donate=True needs extended mode (periodic band "
                         "maps re-read written bands)")
    bh = block_rows or (
        pick_block_rows_extended(wd, steps=T, n_planes=spec.n_planes)
        if extended
        else pick_block_rows(h, wd, steps=T, n_planes=spec.n_planes))
    bw = block_words or wd
    if T > bh:
        raise ValueError(f"steps_per_launch={T} > block_rows={bh}")
    if bw < wd and T > bw:
        raise ValueError(f"steps_per_launch={T} > block_words={bw}")
    if wd % bw:
        raise ValueError(f"block_words={bw} must divide Wd={wd} "
                         f"(the extended path word-pads in run_extended)")
    if interpret is None:
        interpret = jax.default_backend() != "tpu"
    if not interpret and (err := tile_error(bh, bw, h, wd)):
        raise ValueError(f"{err}: the TPU compiler needs the last two "
                         f"block dims to be multiples of ({SUBLANES}, "
                         f"{LANES}) or the array's own extents")
    pq = prng.quantize_p(p_force)

    moment_kw = {}
    if record_steps:
        ms = rulespec.moment_spec(spec, stack_planes=np_)
        n_sites = (hg * wdg if extended else h * wd) * 32
        rulespec.require_moment_headroom(ms, n_sites)
        moment_kw = dict(record_steps=tuple(record_steps),
                         moment_terms=ms.terms, moment_coeffs=ms.coeffs,
                         moment_bounds=moment_bounds)
    step = _k.make_fhp_step(h, wd, bh=bh, bw=bw, pq=pq,
                            rng_in_kernel=rng_in_kernel, interpret=interpret,
                            variant=variant, steps=T, batch=b,
                            extended=extended, donate=donate,
                            static_solid=static_solid, **moment_kw)
    scalars = jnp.stack([jnp.asarray(t, jnp.int32),
                         jnp.asarray(y0, jnp.int32),
                         jnp.asarray(xw0, jnp.int32),
                         jnp.asarray(h if hg is None else hg, jnp.int32),
                         jnp.asarray(wd if wdg is None else wdg,
                                     jnp.int32)]).reshape(1, 5)
    # One binding of the array per overlapping view: 3 row bands, or the
    # 3x3 tile neighbourhood when x is blocked.
    nv = 9 if bw < wd else 3
    args = [scalars] + [planes] * nv
    if static_solid:
        args += [solid] * nv
    if not rng_in_kernel and spec.needs_rng:
        args.append(prng.chirality_words((h, wd), t, y0=y0, xw0=xw0))
        if pq > 0:
            args.append(prng.bernoulli_words((h, wd), t, p_force,
                                             y0=y0, xw0=xw0))
    out = step(*args)
    if record_steps:
        planes_out, mom_part = out
        mom = mom_part.sum(axis=(1, 2))        # cross-block epilogue
        if squeeze:
            return planes_out[0], mom[0]
        return planes_out, mom
    return out[0] if squeeze else out


def _launch_schedule(sizes, offset: int, k: int):
    """Per-launch ``record_steps`` for a record-every-``k`` cadence:
    launch ``j`` of length ``L`` records at in-launch step ``s`` exactly
    when the absolute step count ``offset + done + s + 1`` is a multiple
    of ``k`` (``offset`` carries the cadence phase across calls)."""
    done = 0
    out = []
    for L in sizes:
        out.append(tuple(s for s in range(L)
                         if (offset + done + s + 1) % k == 0))
        done += L
    return out


def run_pallas(planes: jnp.ndarray, steps: int, *, p_force: float = 0.0,
               t0=0, steps_per_launch: int = 1,
               moments_every: int = 0, **kw) -> jnp.ndarray:
    """Advance ``steps`` fused steps (fori_loop carry, donable).

    With ``steps_per_launch`` = T > 1 the plane stack crosses HBM once per
    T steps; the ``steps % T`` trailing steps run as **one** launch with
    ``steps_per_launch = rem`` (one more HBM round trip, not rem of them).
    Bit-identical to the T=1 path for any T (equivalence-tested).

    ``moments_every`` = k > 0 switches on fused observables and returns
    ``(planes, moments)``: the rule's ``MomentSpec`` reductions after
    every k-th step -- ``moments[..., r, :]`` is the state after step
    ``(r + 1) * k`` -- recorded in-kernel at dense cadences (k < T costs
    no extra HBM round trip; the whole point).  The launch loop then
    unrolls in Python (record schedules are per-launch statics), so keep
    ``steps`` modest on the moments path."""
    T = int(steps_per_launch)
    full, rem = divmod(int(steps), T)
    k = int(moments_every)
    if k:
        from repro.core import rulespec
        spec = rulespec.get_rule(kw.get("variant", "fhp2"))
        ms = rulespec.moment_spec(spec, stack_planes=planes.shape[-3])
        sizes = [T] * full + ([rem] if rem else [])
        moms = []
        out = planes
        done = 0
        for L, rs in zip(sizes, _launch_schedule(sizes, 0, k)):
            if rs:
                out, m = fhp_step_pallas(out, t0 + done, p_force=p_force,
                                         steps_per_launch=L,
                                         record_steps=rs, **kw)
                moms.append(m)
            else:
                out = fhp_step_pallas(out, t0 + done, p_force=p_force,
                                      steps_per_launch=L, **kw)
            done += L
        mom = (jnp.concatenate(moms, axis=-2) if moms else
               jnp.zeros(planes.shape[:-3] + (0, ms.n_moments), jnp.int32))
        return out, mom

    def body(i, s):
        return fhp_step_pallas(s, t0 + i * T, p_force=p_force,
                               steps_per_launch=T, **kw)

    out = jax.lax.fori_loop(0, full, body, planes)
    if rem:
        out = fhp_step_pallas(out, t0 + full * T, p_force=p_force,
                              steps_per_launch=rem, **kw)
    return out


def run_extended(ext: jnp.ndarray, steps: int, *, t0=0, p_force: float = 0.0,
                 y0=0, xw0=0, hg: int, wdg: int,
                 steps_per_launch: int | None = None,
                 block_rows: int = 0, block_words: int = 0,
                 solid_ext: jnp.ndarray | None = None,
                 moments_every: int = 0,
                 moments_offset: int = 0, **kw) -> jnp.ndarray:
    """Advance a halo-extended shard array ``steps`` steps in
    ceil(steps / T) extended-mode launches (carry aliased in place when
    the launch is single-band; see ``kernel.make_fhp_step``).

    ``ext`` is the ``(..., 8, He, Wde)`` shard + apron (``He`` rows are
    row-padded here to a block multiple; pad rows sit past the validity
    region and are dropped by the caller's interior slice).  ``y0``/
    ``xw0`` are the global coordinates of ext element (0, 0) -- i.e. of
    the *apron* corner -- and may be traced.  After the call, rows
    ``[steps, He - steps)`` and words ``[1, Wde - 1)`` of the result hold
    the stepped shard (validity shrinks ``steps`` rows per side and one
    lattice column per step; the usual call has ``He = hl + 2*steps``
    so exactly the owned block survives).

    ``solid_ext`` is the static-geometry cache: the (He, Wde) pre-extended
    solid plane of this shard's tile.  ``ext`` then carries only the 7
    dynamic planes, each launch takes the solid as a read-only operand,
    and -- because the cached apron holds the *true* global solid, not a
    validity-shrinking copy -- the same cache serves every launch and
    every exchange round of the geometry's lifetime.

    ``block_words`` (0 = auto) is the 2-D tile width in words: the array
    is word-padded on the right to a block multiple (pad words draw
    deterministic-garbage RNG that contaminates at most one bit per step
    leftward -- it never crosses the outer halo word the validity
    contract already drops).  Auto keeps the legacy full-width 1-D band
    when it fits VMEM and splits x otherwise (``pick_tile_extended``).

    ``moments_every`` = k > 0 returns ``(ext, moments)`` with in-kernel
    ``MomentSpec`` reductions over the final validity window -- rows
    ``[steps, He - steps)`` x words ``[1, Wde - 1)``, i.e. exactly the
    owned shard on the usual ``He = hl + 2*steps`` call -- after every
    step where ``(moments_offset + step + 1) % k == 0``
    (``moments_offset`` carries the cadence phase across exchange
    rounds).  The window is a subset of the valid region at *every*
    intermediate step (validity shrinks monotonically toward it), so
    dense recording inside one exchange round is still bit-exact."""
    from repro.core import rulespec
    n_planes = rulespec.get_rule(kw.get("variant", "fhp2")).n_planes
    steps = int(steps)
    T = int(steps_per_launch or min(steps, MAX_STEPS_PER_LAUNCH))
    he, wde = ext.shape[-2], ext.shape[-1]
    static_solid = solid_ext is not None
    cap = 1
    while cap < he:           # no taller than the array: padding is traffic
        cap *= 2
    bh, bw = block_rows, block_words
    if not bw:
        if bh:
            bw = wde          # legacy callers: explicit rows, full width
        else:
            bh_auto, bw = pick_tile_extended(wde, steps=min(T, steps),
                                             static_solid=static_solid,
                                             n_planes=n_planes)
            bh = min(cap, bh_auto)
    elif not bh:
        bh = min(cap, _pick_bh(wde, min(T, steps), None, block_words=bw,
                               static_solid=static_solid,
                               n_planes=n_planes))
    # Cap *explicit* tiles to the array footprint too: a tuner-chosen
    # block_rows=32 on a thin boundary/remainder slice (e.g. the 3d-row
    # bands of run_extended_split) would otherwise pad the slice up to a
    # full tile -- wasted traffic -- while the cap keeps the launch
    # single-tile so the input_output_aliases donation below still fires.
    bh = min(bh, cap)
    bw = min(bw, wde)
    pad = (-he) % bh
    padw = (-wde) % bw
    if pad or padw:
        widths = [(0, 0)] * (ext.ndim - 2) + [(0, pad), (0, padw)]
        ext = jnp.pad(ext, widths)
    if solid_ext is not None:
        assert solid_ext.shape == (he, wde), (solid_ext.shape, he, wde)
        if pad or padw:
            solid_ext = jnp.pad(solid_ext, [(0, pad), (0, padw)])
    # In-place carry (input_output_aliases) is only race-free when one
    # tile covers the lane: see kernel.make_fhp_step.  The flag rides
    # every launch below -- the full-T main loop *and* the steps % T
    # remainder -- so a trailing short launch aliases its carry too.
    donate = bh == ext.shape[-2] and bw == ext.shape[-1]
    full, rem = divmod(steps, T)
    k = int(moments_every)
    sizes = [T] * full + ([rem] if rem else [])
    schedules = (_launch_schedule(sizes, int(moments_offset), k) if k
                 else [()] * len(sizes))
    # Validity window from the *pre-pad* extents: pad rows/words (indices
    # >= he / wde) fall outside [steps, he-steps) x [1, wde-1) for free.
    bounds = (steps, he - steps, 1, wde - 1)
    moms = []
    done = 0
    with telemetry.span("kernel.extended", steps=steps, launches=len(sizes)):
        for L, rs in zip(sizes, schedules):
            if rs:
                ext, m = fhp_step_pallas(
                    ext, t0 + done, p_force=p_force, y0=y0, xw0=xw0,
                    steps_per_launch=L, block_rows=bh, block_words=bw,
                    extended=True, hg=hg, wdg=wdg, donate=donate,
                    solid=solid_ext, record_steps=rs, moment_bounds=bounds,
                    **kw)
                moms.append(m)
            else:
                ext = fhp_step_pallas(
                    ext, t0 + done, p_force=p_force, y0=y0, xw0=xw0,
                    steps_per_launch=L, block_rows=bh, block_words=bw,
                    extended=True, hg=hg, wdg=wdg, donate=donate,
                    solid=solid_ext, **kw)
            done += L
    if k:
        if moms:
            mom = jnp.concatenate(moms, axis=-2)
        else:
            from repro.core import rulespec
            spec = rulespec.get_rule(kw.get("variant", "fhp2"))
            ms = rulespec.moment_spec(spec, stack_planes=ext.shape[-3])
            mom = jnp.zeros(ext.shape[:-3] + (0, ms.n_moments), jnp.int32)
        return ext[..., :he, :wde], mom
    return ext[..., :he, :wde]


def run_extended_split(ext: jnp.ndarray, steps: int, *, t0=0,
                       p_force: float = 0.0, y0=0, xw0=0, hg: int, wdg: int,
                       steps_per_launch: int | None = None,
                       block_rows: int = 0, block_words: int = 0,
                       solid_ext: jnp.ndarray | None = None,
                       moments_every: int = 0, moments_offset: int = 0,
                       **kw) -> jnp.ndarray:
    """``run_extended`` split into an interior launch plus four thin
    boundary launches, for compute/communication overlap in the sharded
    stepper (``core.distributed``).  Bit-identical to ``run_extended``.

    ``ext`` is the usual ``(..., He, Wde)`` halo-extended shard with
    ``He = hl + 2*steps`` and ``Wde = wdl + 2``.  The **interior** launch
    runs on the bare ``(hl, wdl)`` shard slice -- no halo row or word in
    its footprint, so its dataflow is independent of the exchange that
    produced the apron.  Four **boundary** launches cover the rest:

    * top / bottom: ``3*steps``-row bands at full extended width (halo
      rows + the ``2*steps`` shard rows whose light cone reaches them);
      valid output = shard rows ``[0, d)`` / ``[hl - d, hl)``, all words;
    * left / right: 3-word strips over shard rows ``[d, hl - d)`` (halo
      word + edge word + one interior apron word; ``d <= 31`` column
      shrink stays inside the outer words); valid output = shard word
      ``0`` / ``wdl - 1``.

    Every sub-call reuses ``run_extended`` on a slice with shifted global
    ``y0``/``xw0`` -- the global-mod RNG/parity make apron compute
    bit-exact at any offset, for every registered rule -- and the exact
    valid pieces are concatenated back into the shard (pieces are
    disjoint and exhaustive; no averaging, no halo writeback).  The
    return value keeps ``run_extended``'s ext-shaped contract (rows
    ``[steps, He - steps)`` x words ``[1, Wde - 1)`` valid); the restored
    apron is zero.

    Degenerate shards -- ``hl <= 2*steps`` (boundary bands cover the
    whole shard) or ``wdl <= 2`` (no interior word) -- fall back to the
    serial ``run_extended`` bit-exactly, mirroring the roofline model's
    ``overlap_speedup_modeled == 1.0`` for those shapes.

    ``block_rows``/``block_words`` are the tuner's tile for the interior
    launch; the boundary slices inherit them and rely on ``run_extended``
    capping the tile to each slice's footprint, which also keeps every
    boundary launch single-tile so the ``input_output_aliases`` donation
    fires on each (incl. their ``d % T`` remainder launches).

    ``solid_ext`` slices exactly: the static-geometry cache holds the
    *true* global solid over the whole extended tile, so each sub-slice
    of it is that sub-lattice's correct pre-extended solid operand.

    ``moments_every`` composes exactly: each sub-launch's validity
    window (``run_extended``'s default bounds on its slice) is one of
    five disjoint, exhaustive pieces of the owned shard -- top/bottom
    row bands, left/right edge words, interior -- so the five per-step
    partial moments *sum* to the serial path's shard moments, bit-exact
    (integer adds of disjoint popcounts).  Returns ``(ext, moments)``.
    """
    d = int(steps)
    he, wde = ext.shape[-2], ext.shape[-1]
    hl, wdl = he - 2 * d, wde - 2
    k = int(moments_every)
    mom_kw = dict(moments_every=k, moments_offset=moments_offset) if k else {}
    run = functools.partial(
        run_extended, t0=t0, p_force=p_force, hg=hg, wdg=wdg,
        steps_per_launch=steps_per_launch, block_rows=block_rows,
        block_words=block_words, **mom_kw, **kw)
    if hl <= 2 * d or wdl <= 2:
        return run(ext, d, y0=y0, xw0=xw0, solid_ext=solid_ext)

    moms = []

    def sub(rows, words, y_off, xw_off):
        sl = ext[..., rows, words]
        se = None if solid_ext is None else solid_ext[rows, words]
        out = run(sl, d, y0=y0 + y_off, xw0=xw0 + xw_off, solid_ext=se)
        if k:
            out, m = out
            moms.append(m)
        return out

    with telemetry.span("kernel.interior", steps=d):
        interior = sub(slice(d, he - d), slice(1, wde - 1), d, 1)
    with telemetry.span("kernel.boundary", steps=d):
        top = sub(slice(0, 3 * d), slice(None), 0, 0)
        bot = sub(slice(he - 3 * d, he), slice(None), he - 3 * d, 0)
        left = sub(slice(d, he - d), slice(0, 3), d, 0)
        right = sub(slice(d, he - d), slice(wde - 3, wde), d, wde - 3)

    mid = jnp.concatenate([left[..., d:hl - d, 1:2],
                           interior[..., d:hl - d, 1:wdl - 1],
                           right[..., d:hl - d, 1:2]], axis=-1)
    shard = jnp.concatenate([top[..., d:2 * d, 1:wde - 1],
                             mid,
                             bot[..., d:2 * d, 1:wde - 1]], axis=-2)
    widths = [(0, 0)] * (shard.ndim - 2) + [(d, d), (1, 1)]
    out = jnp.pad(shard, widths)
    if k:
        return out, functools.reduce(jnp.add, moms)
    return out
