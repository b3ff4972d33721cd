"""One large lattice advanced call after call: the paper's workload.

Traffic parameters (``bench/traffic/<mix>.json``, ``kind: lattice``):
``steps_per_call``, the CA steps of one call of the program's entry
(rounded up to a multiple of the sharded stepper's depth), and
``check_bands``, the bands of rows compared with the reference.
Configuration (``lattice`` block): ``height``, ``width``, the fill
(``density`` per FHP channel, or ``east``/``north`` car densities for
BML), the rule and its ``p_force``; ``guarantees.conserved`` lists the
plane groups whose set bits the rule conserves.  A ``mesh`` block
(``shape``, ``axes``, ``max_depth``) shards the lattice over that many
chips: rows over the first axis, words over the second.

Set-up makes the state on the device from the seed, asks the program's
planner for the launch (``ops.autotune_launch``; on a mesh its sharded
search, which also picks the halo depth and the overlap split), builds
the program's entry (``distributed.make_ensemble_run``) and runs one
call to compile and warm it.  The window then calls it on its own
output at t = 0, K, 2K, ... until ``--seconds`` have passed, each call
finished (``block_until_ready``) before the next.
``site_updates_per_s`` is all sites times all steps of the window over
the window's wall time.

Correctness: the window's last call, at its own t0, is compared word for
word with the plain reference (``bench/reference.py``) run from that
call's input, band by band: the band across the lattice's seam, the
bands across the boundaries between shard rows, and bands drawn from the
seed, ``check_bands`` in all, each spanning the whole width (so the
seam and shard boundaries in x too).  The last state must hold the
conserved counts of the first.
"""
from __future__ import annotations

import math
import time

import jax
import jax.numpy as jnp
import numpy as np

from bench import harness, reference

RULES = {"fhp2": 8, "bml": 2}
COMPARE_BAND_ROWS = 4096


def initial_state(cfg: dict, seed: int, sharding=None):
    """The seeded ``(1, n_planes, H, W // 32)`` state, made on the device
    (on a mesh, each shard on its own chip) in one jitted call.  FHP:
    each of the 7 particle channels set with probability ``density``, no
    solid sites.  BML: each site an east car with probability ``east``,
    else a north car with probability ``north``, else empty."""
    lat, rule = cfg["lattice"], cfg["rule"]
    h, wd = lat["height"], lat["width"] // 32

    def make(key):
        rows = jnp.arange(h, dtype=jnp.uint32)[:, None]
        cols = jnp.arange(wd, dtype=jnp.uint32)[None, :]
        if rule == "bml":
            planes = reference.seeded_fill(key, 0, rows, cols,
                                           [lat["east"], lat["north"]])
        else:
            planes = [reference.seeded_fill(key, 1 + c, rows, cols,
                                            [lat["density"]])[0]
                      for c in range(7)]
            planes.append(jnp.zeros((h, wd), jnp.uint32))
        return jnp.stack(planes)[None]

    return jax.jit(make, out_shardings=sharding)(reference.seed_key(seed))


def conserved(cfg: dict, planes) -> list:
    counts = reference.plane_counts(planes)
    return [sum(counts[p] for p in group)
            for group in cfg["guarantees"]["conserved"]]


def build(cell: harness.Cell):
    """The program's compiled entry for this lattice, the sharding its
    state lives in (None on one chip), its plan and the steps of a
    call."""
    from repro.core import distributed
    from repro.kernels.fhp_step import ops
    cfg, lat = cell.config, cell.config["lattice"]
    wd = lat["width"] // 32
    n = RULES[cfg["rule"]]
    kw = dict(variant=cfg["rule"], p_force=cfg.get("p_force", 0.0),
              use_pallas=True)
    mesh_cfg = cfg.get("mesh")
    if mesh_cfg is None:
        mesh = None
        bh, bw, T = ops.autotune_launch(lat["height"], wd, n_planes=n)
        plan = {"block_rows": bh, "block_words": bw, "steps_per_launch": T}
    else:
        ny, nx = mesh_cfg["shape"]
        mesh = distributed.make_mesh((ny, nx), mesh_cfg["axes"],
                                     devices=cell.devices)
        bh, bw, T, depth, overlap = ops.autotune_launch(
            lat["height"] // ny, wd // nx, n_planes=n,
            max_depth=int(mesh_cfg["max_depth"]))
        plan = {"block_rows": bh, "block_words": bw, "steps_per_launch": T,
                "depth": depth, "overlap": overlap}
        kw.update(depth=depth, overlap=overlap,
                  y_axes=(mesh_cfg["axes"][0],), x_axis=mesh_cfg["axes"][1])
    depth = plan.get("depth", 1)
    steps = depth * math.ceil(int(cell.traffic["steps_per_call"]) / depth)
    run, sharding = distributed.make_ensemble_run(
        mesh, steps, steps_per_launch=T, block_rows=bh, block_words=bw, **kw)
    return jax.jit(run), sharding, plan, steps


def check_bands(cell: harness.Cell, rows: int) -> list:
    """First rows of the bands compared: bands of ``rows`` rows centred
    on multiples of ``rows``; the one across the seam (row 0) and those
    across the boundaries between shard rows always, the rest drawn from
    the seed, ``check_bands`` in all."""
    h = cell.config["lattice"]["height"]
    ny = cell.config.get("mesh", {}).get("shape", [1])[0]
    n_bands = math.ceil(h / rows)
    fixed = sorted({round(j * h / ny / rows) % n_bands for j in range(ny)})
    others = [k for k in range(n_bands) if k not in fixed]
    want = max(int(cell.traffic["check_bands"]) - len(fixed), 0)
    rng = np.random.default_rng(cell.seed)
    drawn = rng.choice(len(others), size=min(want, len(others)),
                       replace=False) if others else []
    return [(k * rows - rows // 2) % h
            for k in fixed + sorted(others[i] for i in drawn)]


def cut_rows(state, r0: int, rows: int, device):
    """Rows ``[r0, r0 + rows)`` (wrapped) of a ``(1, n, H, Wd)`` state,
    the whole width, as one ``(n, rows, Wd)`` array on ``device``: pieced
    together from the shards that hold them, so a sharded state is never
    gathered whole."""
    h = state.shape[-2]
    shards = sorted(state.addressable_shards,
                    key=lambda s: (s.index[-2].start or 0,
                                   s.index[-1].start or 0))
    pieces, pos, left = [], r0 % h, rows
    while left:
        a, b = pos, min(pos + left, h)
        for ys in sorted({s.index[-2].start or 0 for s in shards}):
            row = [s for s in shards if (s.index[-2].start or 0) == ys]
            ye = ys + row[0].data.shape[-2]
            if max(a, ys) < min(b, ye):
                pieces.append(jnp.concatenate(
                    [jax.device_put(s.data[0, :, max(a, ys) - ys:
                                           min(b, ye) - ys], device)
                     for s in row], axis=-1))
        left -= b - a
        pos = 0
    return jnp.concatenate(pieces, axis=-2)


def reference_bands(cfg: dict, steps: int, p_force: float, rows: int,
                    device):
    """``band(state, t0, r0)``: rows ``[r0, r0 + rows)`` of the
    reference's ``steps`` steps of ``state`` from ``t0``, run on
    ``device`` from a band with an apron of ``steps`` rows each side."""
    h = cfg["lattice"]["height"]
    run = jax.jit(lambda ext, idx, t0: reference.run_band(
        ext, idx, t0, steps=steps, rule=cfg["rule"], p_force=p_force))

    def band(state, t0, r0):
        idx = reference.band_rows(h, r0, rows, steps)
        return run(cut_rows(state, r0 - steps, rows + 2 * steps, device),
                   jax.device_put(idx, device), t0)

    return band


def mismatched_words(got, want, r0s) -> int:
    """Words in which the bands ``got(r0)`` and ``want(r0)`` differ, over
    the bands starting at ``r0s``."""
    diff = jax.jit(lambda a, b: jnp.sum(a != b, dtype=jnp.int32))
    return sum(int(diff(got(r0), want(r0))) for r0 in r0s)


def run(cell: harness.Cell) -> harness.Outcome:
    cfg, lat = cell.config, cell.config["lattice"]
    p_force = float(cfg.get("p_force", 0.0))
    sites = lat["height"] * lat["width"]
    marks = [("start", time.perf_counter() - cell.started)]
    with jax.profiler.TraceAnnotation("bench.setup"):
        step, sharding, plan, steps = build(cell)
        marks.append(("plan", time.perf_counter() - cell.started))
        cell.log(f"plan: {cfg['rule']} {lat['height']}x{lat['width']} "
                 f"on {cell.chips} chip(s) {plan}, {steps} steps per call")
        state = initial_state(cfg, cell.seed, sharding)
        before = conserved(cfg, state)
        marks.append(("state", time.perf_counter() - cell.started))
        jax.block_until_ready(step(state, 0))
    setup_s = time.perf_counter() - cell.started
    marks.append(("warm call", setup_s))
    cell.log("setup: " + ", ".join(f"{k} at {v:.3f} s" for k, v in marks))

    profiler = harness.Profiler(cell.trace, f"{cell.workdir}/trace")
    calls = 0
    with profiler:
        with jax.profiler.TraceAnnotation("bench.window"):
            t0 = time.perf_counter()
            while True:
                prev = state
                with jax.profiler.TraceAnnotation("bench.call"):
                    state = jax.block_until_ready(step(prev, calls * steps))
                calls += 1
                window_s = time.perf_counter() - t0
                if window_s >= cell.seconds:
                    break
    rate = sites * steps * calls / window_s / 1e9
    cell.log(f"window: {calls} calls of {steps} steps in {window_s:.6f} s: "
             f"{rate:.4f} Gsite/s")
    peak = harness.memory_peak(cell.devices)
    after = conserved(cfg, state)

    rows = min(COMPARE_BAND_ROWS, lat["height"])
    r0s = check_bands(cell, rows)
    last_t0 = (calls - 1) * steps
    cell.log(f"check: the last call (t0 {last_t0}) in the {len(r0s)} bands "
             f"of {rows} rows starting at rows {r0s}")
    dev = cell.devices[0]
    want = reference_bands(cfg, steps, p_force, rows, dev)
    checks = [harness.Check("mismatched_words", mismatched_words(
                  lambda r0: cut_rows(state, r0, rows, dev),
                  lambda r0: want(prev, last_t0, r0), r0s), 0),
              harness.Check("conserved_drift",
                            sum(abs(a - b) for a, b in zip(after, before)),
                            0)]
    control = {}
    if cell.control:
        # The reference in the program's place, with one guarantee
        # broken: the configuration's body force left out, or, for a
        # rule without one, the call's last step.
        broken = reference_bands(cfg, steps - (0 if p_force else 1), 0.0,
                                 rows, dev)
        control["mismatched_words"] = mismatched_words(
            lambda r0: broken(prev, last_t0, r0),
            lambda r0: want(prev, last_t0, r0), r0s)
    del prev, state
    counts = {"site_updates": sites * steps * calls,
              "ca_steps": steps * calls, "chips": cell.chips}
    return harness.Outcome(
        end_to_end={"site_updates_per_s": rate, "setup_s": setup_s},
        checks=checks, attempted=calls, failed=0, memory_peak_bytes=peak,
        readings={"trace": profiler.reduce(), "counts": counts,
                  "spans": None},
        control=control)
