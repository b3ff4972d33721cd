"""Distributed FHP stepping: explicit domain decomposition over the mesh.

This is the TPU analogue of the paper's two coarse-grained schemes:

* PThreads row bands with two barriers per step (CPU)  ->  ``shard_map``
  over the ``(pod, data)`` mesh axes in y and ``model`` in x, with halo
  exchange via ``jax.lax.ppermute`` (pure nearest-neighbour ICI traffic,
  the natural mapping onto the TPU torus);
* CUDA overlapping blocks A/B/C (GPU)  ->  each shard *reads* an extended
  rectangle (own block + halo) and *writes* its disjoint block, exactly the
  paper's Fig. 7/8 ownership discipline, lifted from thread blocks to chips.

Halo-widening (beyond-paper): exchanging a depth-``d`` halo allows ``d``
local steps per exchange, trading a little redundant compute at the seams
for 1/d of the exchange *count* (latency-bound at scale).  The validity
region of the extended array shrinks by one row and one lattice column per
local step, so ``d`` rows of y-halo and one 32-node word of x-halo support
any ``d <= 31``.  ``overlap=True`` additionally splits each round into an
interior launch (apron-independent, overlaps the ``ppermute`` ring) plus
thin boundary launches -- ``max(t_exchange, t_interior) + t_boundary``
instead of the serial sum (see ``make_sharded_stepper``).

Counter-based RNG makes every scheme bit-identical to the single-device
reference: shards hash *global* (row, word, t) coordinates (mod the global
extent, so halo regions reproduce the owning shard's stream exactly).

Static-geometry cache: obstacle scenarios carry a solid plane that the
update never changes, yet the naive scheme re-exchanges its halo every
round.  ``make_solid_cache`` exchanges the solid plane's depth-apron
**once per geometry** and keeps the per-shard extended tile; the
``static_solid`` stepper then moves only the 7 dynamic planes per round
(a 7/8 cut of exchange bytes) and hands the cached tile to the kernel as
a read-only operand (``kernels/fhp_step`` static-solid mode, which also
drops the solid plane from the HBM writeback).  The cached apron holds
the *true* global solid -- not a validity-shrinking copy -- so one cache
serves every launch, round, and ensemble lane for the geometry's
lifetime.
"""
from __future__ import annotations

import functools
from typing import Sequence, Tuple, Union

import jax
import jax.numpy as jnp
from jax import lax
from jax.sharding import NamedSharding, PartitionSpec as P

from repro.core import prng, rulespec

Axes = Union[str, Tuple[str, ...]]


def _shard_map(f, mesh, in_specs, out_specs):
    # Replication checking is off: pallas_call's out_shape carries no
    # replication metadata; correctness is established by the
    # bit-exactness tests.
    return jax.shard_map(f, mesh=mesh, in_specs=in_specs,
                         out_specs=out_specs, check_vma=False)


def make_mesh(shape: Sequence[int], axes: Sequence[str], *, devices=None):
    """The device mesh every CA path runs on: ``jax.make_mesh`` with
    ``Auto`` axis types.  (``jax.make_mesh`` defaults to ``Explicit``
    axes, under which the engine's ``device_put``, the ``psum``'d
    moments and the GSPMD baseline's sharding constraints all fail.)"""
    return jax.make_mesh(tuple(shape), tuple(axes),
                         axis_types=(jax.sharding.AxisType.Auto,) * len(axes),
                         devices=devices)


def _mesh_size(mesh, axes: Axes) -> int:
    """Static product of mesh extents over one axis name or a tuple."""
    if isinstance(axes, str):
        return mesh.shape[axes]
    n = 1
    for a in axes:
        n *= mesh.shape[a]
    return n


def lattice_spec(y_axes: Axes = ("data",), x_axis: str = "model",
                 batched: bool = False) -> P:
    """PartitionSpec of a (8, H, Wd) plane stack: rows over y_axes, words
    over x_axis, the 8 planes replicated (they live together per node).
    ``batched`` prepends a replicated ensemble-lane axis for
    (B, 8, H, Wd) stacks."""
    if batched:
        return P(None, None, y_axes, x_axis)
    return P(None, y_axes, x_axis)


def _ring(n: int, up: bool):
    return [(k, (k + 1) % n) for k in range(n)] if up else \
           [(k, (k - 1) % n) for k in range(n)]


def _exchange_halo(planes, d: int, ny: int, nx: int, y_axes: Axes,
                   x_axis: str):
    """x halo first (one word each side), then y halo on the x-extended
    array -- the corner words ride along with the y rows."""
    left = lax.ppermute(planes[..., -1:], x_axis, _ring(nx, up=True))
    right = lax.ppermute(planes[..., :1], x_axis, _ring(nx, up=False))
    ext = jnp.concatenate([left, planes, right], axis=-1)
    top = lax.ppermute(ext[..., -d:, :], y_axes, _ring(ny, up=True))
    bot = lax.ppermute(ext[..., :d, :], y_axes, _ring(ny, up=False))
    return jnp.concatenate([top, ext, bot], axis=-2)


def make_solid_cache(mesh, *, y_axes: Axes = ("data",),
                     x_axis: str = "model", depth: int = 1):
    """Build ``extend(solid) -> solid_ext``: the one-per-geometry halo
    exchange of the static solid plane.

    ``solid`` is the (H, Wd)-sharded packed solid plane; the result holds
    each shard's (hl + 2*depth, wdl + 2) extended tile (global shape
    (ny*(hl+2d), nx*(wdl+2)) under the same spec).  Feed it to the
    ``static_solid`` stepper every round -- the dynamic exchange then
    moves 7 planes instead of 8.  Because the solid never changes, the
    apron is exact for the geometry's whole lifetime; rebuild only when
    the geometry changes."""
    ny, nx = _mesh_size(mesh, y_axes), _mesh_size(mesh, x_axis)

    def ext_fn(solid: jnp.ndarray) -> jnp.ndarray:
        assert depth <= solid.shape[-2], \
            f"depth={depth} > local rows {solid.shape[-2]}"
        return _exchange_halo(solid, depth, ny, nx, y_axes, x_axis)

    return _shard_map(ext_fn, mesh, (P(y_axes, x_axis),), P(y_axes, x_axis))


def make_sharded_stepper(mesh, *, y_axes: Axes = ("data",),
                         x_axis: str = "model", p_force: float = 0.0,
                         depth: int = 1, use_pallas: bool = False,
                         batched: bool = False,
                         steps_per_launch: int | None = None,
                         block_rows: int = 0, block_words: int = 0,
                         static_solid: bool = False,
                         overlap: bool = False,
                         variant: str = "fhp2",
                         moments_every: int = 0):
    """Build ``step(planes, t) -> planes`` advancing ``depth`` global CA
    steps per halo exchange under ``shard_map``.

    ``variant`` names the registered rule (``core.rulespec``): the plane
    stack is ``(..., spec.n_planes, H, Wd)`` and both the Pallas and the
    jnp-fallback local updates run that rule's streaming stencil and
    collision circuit.  Every tap honours the one-row/one-word halo
    contract, so the exchange machinery is rule-agnostic.

    ``use_pallas`` runs the local update with the fused Pallas kernel in
    extended-shard mode for any ``depth``: the kernel's RNG / parity
    counters reduce **global** coordinates mod the global extents, so the
    apron rows of the exchanged halo draw the owning shard's stream and
    one depth-``d`` exchange feeds ``d`` in-kernel steps --
    ``ceil(d / steps_per_launch)`` fused launches with a donated carry
    (``steps_per_launch`` defaults to ``min(depth, MAX_STEPS_PER_LAUNCH)``;
    ``block_rows`` / ``block_words`` 0 = auto -- a non-zero
    ``block_words`` below the extended shard width selects the 2-D
    (x x y) blocked kernel grid, which lifts the VMEM ceiling on wide
    shards; the autotuned tile from ``ops.autotune_launch`` passes
    through unchanged).  The sharded hot path thus compounds the
    T-fold HBM-traffic cut of temporal blocking with the 1/d exchange
    count of halo-widening.  ``batched`` steps a (B, 8, H, Wd) ensemble
    stack (lanes replicated over the mesh, sharded in H/Wd like the
    unbatched case).

    ``overlap`` (Pallas path only) runs each round through
    ``ops.run_extended_split``: an **interior** launch on the bare shard
    -- whose ``depth``-step light cone never touches the exchanged apron
    -- plus four thin boundary launches (top/bottom row bands, left/right
    word strips) that are the only consumers of the halo.  The split is
    bit-exact vs the serial path by construction (exact-piece
    composition; degenerate shards fall back to ``run_extended``), so
    the scheduler is free to overlap: the interior launch depends only
    on the previous round's composed shard, not on this round's
    ``ppermute``, so compute and exchange proceed concurrently.  The
    double-buffering falls out of the dataflow rather than explicit
    buffer management: round k+1's halo slices (``planes[..., :d]``,
    ``planes[..., -d:]``, the edge word columns) align exactly with the
    boundary pieces of round k's composition, so XLA's slice-of-concat
    folding sources the next exchange from the boundary launches' output
    buffers directly -- the ring for round k+1 issues as soon as round
    k's *boundary* launches land, hiding under round k+1's interior
    compute.  (On the interpret-mode CPU backend the launches serialize,
    so timed overlap numbers there measure split overhead only; see
    EXPERIMENTS.md.)

    ``static_solid`` returns ``step(dyn, solid_ext, t) -> dyn`` instead:
    ``dyn`` is the (..., 7, H, Wd) *dynamic* plane stack and ``solid_ext``
    the cached extended solid tiles from ``make_solid_cache`` (same
    depth).  Each round then exchanges 7 planes instead of 8; batched
    lanes share the one geometry.

    ``moments_every`` = k > 0 (k must divide ``depth``) makes the stepper
    return ``(planes, moments)``: per-shard partial ``MomentSpec``
    reductions recorded in-kernel every k-th step of the round (the jnp
    fallback computes them post-step on the owned slice, bit-identically)
    and ``psum``'d over every mesh axis, so each device holds the
    replicated global ``(..., depth // k, n_moments)`` int32 time series.
    The layout is ``moment_spec(rule)`` -- with ``static_solid`` the
    7-plane stack drops the ``solid`` row (``stack_planes = n_planes-1``).

    The returned function is shard_map'ed but not jitted; callers compose it
    (e.g. ``lax.fori_loop`` over exchanges) and jit the whole program.
    """
    assert 1 <= depth <= 31, "x halo is one 32-node word -> depth <= 31"
    assert not overlap or use_pallas, \
        "overlap splits Pallas launches: needs use_pallas=True"
    rule = rulespec.get_rule(variant)
    assert not static_solid or rule.solid_plane is not None, \
        f"rule {variant!r} has no solid plane: static_solid unavailable"
    assert p_force == 0.0 or rule.force is not None, \
        f"rule {variant!r} has no force pass: p_force must be 0"
    k = int(moments_every)
    assert k == 0 or depth % k == 0, \
        f"moments_every={k} must divide depth={depth} (static cadence)"
    if k:
        mspec = rulespec.moment_spec(
            rule, stack_planes=rule.n_planes - 1 if static_solid else None)
    spec = lattice_spec(y_axes, x_axis, batched=batched)
    ny, nx = _mesh_size(mesh, y_axes), _mesh_size(mesh, x_axis)
    psum_axes = ((y_axes,) if isinstance(y_axes, str) else tuple(y_axes)) \
        + (x_axis,)

    def chunk(planes: jnp.ndarray, solid_ext, t) -> jnp.ndarray:
        iy, ix = lax.axis_index(y_axes), lax.axis_index(x_axis)
        hl, wdl = planes.shape[-2:]
        d = depth
        # The ring ppermute reaches nearest neighbours only: a depth-d
        # apron must fit in one shard's rows or the halo slices clamp
        # short and the validity accounting silently breaks.
        assert d <= hl, f"depth={d} > local rows hl={hl}: halo would " \
                        f"need rows beyond the nearest-neighbour shard"
        if static_solid:
            assert solid_ext.shape == (hl + 2 * d, wdl + 2), \
                (solid_ext.shape, hl, wdl, d)

        ext = _exchange_halo(planes, d, ny, nx, y_axes, x_axis)

        if use_pallas:
            from repro.kernels.fhp_step.ops import (run_extended,
                                                    run_extended_split)
            advance = run_extended_split if overlap else run_extended
            # Global coordinates of ext element (0, 0) (the apron corner)
            # and the global extents the kernel's RNG reduces mod.
            out = advance(ext, d, t0=t, p_force=p_force,
                          y0=iy * hl - d, xw0=ix * wdl - 1,
                          hg=ny * hl, wdg=nx * wdl,
                          steps_per_launch=steps_per_launch,
                          block_rows=block_rows,
                          block_words=block_words, solid_ext=solid_ext,
                          variant=variant, moments_every=k)
            if k:
                out, mom = out
                return (out[..., d:d + hl, 1:1 + wdl],
                        lax.psum(mom, psum_axes))
            return out[..., d:d + hl, 1:1 + wdl]

        if static_solid:
            # jnp fallback: rebuild the 8-plane stack from the cache (the
            # exchange saving stands; only the local update is fused-off).
            sol = jnp.broadcast_to(solid_ext,
                                   ext.shape[:-3] + (1,) + solid_ext.shape)
            ext = jnp.concatenate([ext, sol], axis=-3)

        # Global coordinates (mod global extent) of every ext row/word: the
        # RNG draws of halo cells must match the owning shard's draws.
        rows = (jnp.arange(hl + 2 * d) + iy * hl - d) % (ny * hl)
        cols = (jnp.arange(wdl + 2) + ix * wdl - 1) % (nx * wdl)
        rows, cols = rows[:, None], cols[None, :]
        row0 = iy * hl - d  # parity offset (global H is even; sign-safe)

        def one(s, tt):
            words = prng.random_words_at(rows, cols, tt, rule.random_words)
            acc = (prng.bernoulli_words_at(rows, cols, tt, p_force)
                   if p_force > 0 else None)
            return rulespec.step_planes_rule(s, tt, rule, y0=row0,
                                             words=words, accel=acc)

        if k:
            # Moments cadence: Python-unrolled round (depth is small) --
            # the fallback steps the full extended array, whose owned
            # region is correct at every step, so recording the owned
            # slice matches the in-kernel path bit-exactly.
            moms = []
            for j in range(d):
                ext = one(ext, t + j)
                if (j + 1) % k == 0:
                    own = ext[..., d:d + hl, 1:1 + wdl]
                    if static_solid:
                        own = own[..., :rule.n_planes - 1, :, :]
                    moms.append(rulespec.compute_moments(own, mspec))
            mom = lax.psum(jnp.stack(moms, axis=-2), psum_axes)
        elif d == 1:
            ext = one(ext, t)
        else:
            ext = lax.fori_loop(0, d, lambda j, s: one(s, t + j), ext)
        if static_solid:
            ext = ext[..., :rule.n_planes - 1, :, :]
        if k:
            return ext[..., d:d + hl, 1:1 + wdl], mom
        return ext[..., d:d + hl, 1:1 + wdl]

    out_spec = (spec, P()) if k else spec     # psum'd moments: replicated
    if static_solid:
        return _shard_map(chunk, mesh, (spec, P(y_axes, x_axis), P()),
                          out_spec)
    return _shard_map(lambda planes, t: chunk(planes, None, t), mesh,
                      (spec, P()), out_spec)


def make_run(mesh, steps: int, **kw):
    """Jittable ``run(planes, t0)`` advancing ``steps`` global steps.

    With ``static_solid=True`` the caller still passes the full 8-plane
    stack: the solid plane is split off, its apron exchanged **once**
    (``make_solid_cache`` -- hoisted out of the exchange loop under jit),
    and the loop advances the 7 dynamic planes against the cached tile;
    the unchanged solid plane is stitched back into the result.  Batched
    stacks share lane 0's geometry (ensemble diversity enters through the
    initial conditions, not the obstacles).

    With ``moments_every`` = k (must divide ``depth``) the result is
    ``(planes, moments)``: each round's ``depth // k`` fused records land
    in a preallocated ``(..., steps // k, n_moments)`` buffer via
    ``dynamic_update_slice`` inside the round loop."""
    depth = kw.get("depth", 1)
    static_solid = kw.get("static_solid", False)
    rule = rulespec.get_rule(kw.get("variant", "fhp2"))
    sp = rule.solid_plane
    k = int(kw.get("moments_every", 0))
    assert steps % depth == 0, (steps, depth)
    stepper = make_sharded_stepper(mesh, **kw)
    if k:
        mspec = rulespec.moment_spec(
            rule, stack_planes=rule.n_planes - 1 if static_solid else None)
        r_round = depth // k

    def loop(state, step_round):
        """fori_loop over rounds; with moments, the carry grows a record
        buffer each round writes its ``r_round`` rows into."""
        if not k:
            return lax.fori_loop(0, steps // depth,
                                 lambda i, s: step_round(i, s), state)
        buf = jnp.zeros(state.shape[:-3] + (steps // k, mspec.n_moments),
                        jnp.int32)

        def body(i, carry):
            s, b = carry
            s, m = step_round(i, s)
            starts = (0,) * (b.ndim - 2) + (i * r_round, 0)
            return s, lax.dynamic_update_slice(b, m, starts)

        return lax.fori_loop(0, steps // depth, body, (state, buf))

    if not static_solid:
        def run(planes, t0):
            return loop(planes, lambda i, s: stepper(s, t0 + i * depth))

        return run

    cache = make_solid_cache(mesh, y_axes=kw.get("y_axes", ("data",)),
                             x_axis=kw.get("x_axis", "model"), depth=depth)
    batched = kw.get("batched", False)

    def run(planes, t0):
        dyn = planes[..., :sp, :, :]
        solid = planes[..., sp, :, :]
        if batched:
            solid = solid[0]          # lanes share the geometry
        solid_ext = cache(solid)      # one exchange per geometry

        out = loop(dyn, lambda i, s: stepper(s, solid_ext, t0 + i * depth))
        dyn, mom = out if k else (out, None)
        planes = jnp.concatenate([dyn, planes[..., sp:, :, :]], axis=-3)
        return (planes, mom) if k else planes

    return run


def make_ensemble_run(mesh, steps: int, *, variant: str = "fhp2",
                      p_force: float = 0.0, depth: int = 1,
                      use_pallas: bool = False,
                      steps_per_launch: int | None = None,
                      block_rows: int = 0, block_words: int = 0,
                      overlap: bool = False, y_axes: Axes = ("data",),
                      x_axis: str = "model", moments_every: int = 0):
    """``(run, sharding)`` for a batched ``(B, n_planes, H, Wd)`` ensemble:
    the serve engine's one entry point for advancing a lane group.

    ``run(planes, t0)`` advances every lane ``steps`` global CA steps
    under ``variant``; lanes are independent and the RNG counters carry
    no lane index, so each lane is bit-identical to the unbatched
    reference at the same ``t`` window (the engine's rollback-replay and
    job-vs-reference audits both lean on this).

    ``mesh=None`` is the single-device path (``sharding`` is None):
    the fused Pallas kernel when ``use_pallas`` else the jnp bit-plane
    fallback.  With a mesh, the sharded halo-exchange stepper runs with
    the given ``(depth, T, blocks, overlap)`` point and ``sharding`` is
    the batched lattice ``NamedSharding`` to place states with.

    ``moments_every`` = k > 0 makes ``run`` return ``(planes, moments)``
    with ``moments`` the per-lane ``(B, steps // k, n_moments)`` int32
    fused ``MomentSpec`` time series -- recorded in-kernel on the Pallas
    paths, post-step on the jnp fallback, identical layouts
    (``rulespec.moment_spec(rule)``); on a mesh, k must divide ``depth``.
    The serve engine reads its per-round audits straight from this.
    """
    k = int(moments_every)
    if mesh is None:
        rule = rulespec.get_rule(variant)
        if use_pallas:
            from repro.kernels.fhp_step import ops

            def run(planes, t0):
                return ops.run_pallas(
                    planes, steps, p_force=p_force, t0=t0,
                    steps_per_launch=steps_per_launch or 1,
                    block_rows=block_rows, block_words=block_words,
                    variant=variant, moments_every=k)
        elif k:
            mspec = rulespec.moment_spec(rule)

            def run(planes, t0):
                s = planes
                moms = []
                for j in range(int(steps)):
                    s = rulespec.run_planes_rule(s, 1, rule,
                                                 p_force=p_force, t0=t0 + j)
                    if (j + 1) % k == 0:
                        moms.append(rulespec.compute_moments(s, mspec))
                mom = (jnp.stack(moms, axis=-2) if moms else
                       jnp.zeros(planes.shape[:-3] + (0, mspec.n_moments),
                                 jnp.int32))
                return s, mom
        else:
            def run(planes, t0):
                return rulespec.run_planes_rule(planes, steps, rule,
                                                p_force=p_force, t0=t0)
        return run, None
    run = make_run(mesh, steps, y_axes=y_axes, x_axis=x_axis,
                   p_force=p_force, depth=depth, use_pallas=use_pallas,
                   batched=True, steps_per_launch=steps_per_launch,
                   block_rows=block_rows, block_words=block_words,
                   overlap=overlap, variant=variant, moments_every=k)
    sharding = NamedSharding(mesh, lattice_spec(y_axes, x_axis,
                                                batched=True))
    return run, sharding


def make_gspmd_run(mesh, steps: int, *, y_axes: Axes = ("data",),
                   x_axis: str = "model", p_force: float = 0.0,
                   batched: bool = False, variant: str = "fhp2"):
    """Baseline distribution: the *global* stepper under jit + sharding
    constraints; GSPMD materialises the halo traffic as collective-permutes
    of the roll/shift edge slices.  Used as the §Perf baseline against the
    explicit shard_map/ppermute scheme above."""
    rule = rulespec.get_rule(variant)
    spec = lattice_spec(y_axes, x_axis, batched=batched)
    sharding = NamedSharding(mesh, spec)

    def run(planes, t0):
        planes = lax.with_sharding_constraint(planes, sharding)

        def body(i, s):
            s = rulespec.step_planes_rule(s, t0 + i, rule, p_force=p_force)
            return lax.with_sharding_constraint(s, sharding)

        return lax.fori_loop(0, steps, body, planes)

    return run
