"""Run the main path once on a TPU and check what comes out.

    python chip_smoke.py             # one chip
    python chip_smoke.py --chips 4   # the sharded path on a 2x2 host

One chip: the fused Pallas kernel advances FHP-II with forcing, then
BML, on a 16384 x 16384-site periodic lattice through
``core.distributed.make_ensemble_run`` (the path the serve engine uses)
with the autotuner's tile; each result must be bit-identical to the
independent jnp plane stepper run on the same chip, with every conserved
quantity kept.  Then the serve engine drains 8 jobs (cylinder flow and
BML traffic on 4096 x 4096 lattices) with audits and checkpoints on;
the last frame of one job per rule must equal the jnp stepper's.

``--chips 4`` runs only the sharded phase: ``make_ensemble_run`` on a
2x2 mesh over a 32768 x 16384-site lattice at depth 4, overlap off and
on, each chip holding one quarter, bit-exact against the single-chip
kernel; then a short engine drain on that mesh.

Every phase checks that its compiled step holds a Mosaic kernel
(``tpu_custom_call``), so nothing ran in interpret mode.  Timings
printed are smoke timings of one run, not benchmark numbers.  The last
line of output is one JSON object naming the device; the script exits
non-zero, without that line, when any check fails or no TPU is found.
"""
from __future__ import annotations

import argparse
import json
import sys
import tempfile
import time
from pathlib import Path

sys.path.insert(0, str(Path(__file__).resolve().parent / "src"))


class SmokeFailure(Exception):
    pass


def check(ok, what: str) -> None:
    if not ok:
        raise SmokeFailure(what)


def log(msg: str) -> None:
    print(msg, flush=True)


def require_mosaic(lowered, what: str) -> None:
    """The lowered step must call the compiled Pallas kernel."""
    check("tpu_custom_call" in lowered.as_text(),
          f"{what}: no tpu_custom_call in the lowered step (interpreted?)")
    log(f"{what}: tpu_custom_call present")


def random_planes(variant: str, shape, seed: int, sharding=None):
    """A seeded random state of ``variant`` made on the device: FHP fills
    each of the 7 particle channels with probability 1/2 (no solid
    sites); BML puts east cars at density 1/4 and north cars at 1/8,
    never both on one site."""
    import jax
    import jax.numpy as jnp

    from repro.core import rulespec
    n = rulespec.get_rule(variant).n_planes

    def make():
        key = jax.random.key(seed)
        if variant == "bml":
            a, b, c = jax.random.bits(key, (3,) + shape, jnp.uint32)
            planes = [a & b, ~a & b & c]
        else:
            planes = list(jax.random.bits(key, (n - 1,) + shape,
                                          jnp.uint32))
            planes.append(jnp.zeros(shape, jnp.uint32))
        return jnp.stack(planes, axis=-3)

    return jax.jit(make, out_shardings=sharding)()


def conserved(variant: str, planes) -> dict:
    import numpy as np

    from repro.core import rulespec
    inv = rulespec.invariants(rulespec.get_rule(variant), planes)
    return {k: np.asarray(v).tolist() for k, v in inv.items()}


def timed(fn, *args):
    import jax
    t0 = time.perf_counter()
    out = jax.block_until_ready(fn(*args))
    return out, time.perf_counter() - t0


def large_lattice_phase(kind: str, variant: str, p_force: float,
                        h: int, w: int, steps: int, seed: int) -> None:
    """``steps`` kernel steps on one ``h x w`` lattice through the
    single-device ensemble path, against the jnp plane stepper."""
    import jax
    import jax.numpy as jnp

    from repro.core import distributed, rulespec
    from repro.kernels.fhp_step import ops
    spec = rulespec.get_rule(variant)
    wd = w // 32
    bh, bw, T = ops.autotune_launch(h, wd, n_planes=spec.n_planes)
    tag = f"[{kind}] {variant} {h}x{w} p_force={p_force}"
    log(f"{tag}: tile block_rows={bh} block_words={bw} "
        f"steps_per_launch={T}, {steps} steps")
    planes = random_planes(variant, (1, h, wd), seed)
    before = conserved(variant, planes)
    run, _ = distributed.make_ensemble_run(
        None, steps, variant=variant, p_force=p_force, use_pallas=True,
        steps_per_launch=T, block_rows=bh, block_words=bw)
    lowered = jax.jit(run).lower(planes, 0)
    require_mosaic(lowered, f"{tag} kernel step")
    t0 = time.perf_counter()
    kernel = lowered.compile()
    log(f"{tag}: kernel compile {time.perf_counter() - t0:.3f} s")
    out, dt = timed(kernel, planes, 0)
    log(f"{tag}: kernel run {dt:.6f} s")

    ref_fn = jax.jit(lambda p, t0: rulespec.run_planes_rule(
        p, steps, spec, p_force=p_force, t0=t0))
    ref, dt = timed(ref_fn, planes, 0)
    log(f"{tag}: jnp reference (compile + run) {dt:.3f} s")
    check(not bool(jnp.array_equal(ref, planes)),
          f"{tag}: the reference did not move the state")
    check(bool(jnp.array_equal(out, ref)),
          f"{tag}: Pallas result differs from the jnp stepper")
    after = conserved(variant, out)
    check(after == before, f"{tag}: conserved {before} -> {after}")
    log(f"{tag}: bit-identical to the jnp stepper; conserved {after}")


def drain_engine_phase(kind: str, h: int, w: int, jobs: int, steps: int,
                       mesh=None) -> None:
    """Drain ``jobs`` jobs (cylinder and bml_city alternating) through
    the serve engine with audits and checkpoints on; check one job per
    rule against the jnp stepper."""
    import jax
    import numpy as np

    from repro import scenarios
    from repro.checkpoint import store
    from repro.core import rulespec
    from repro.scenarios import observables
    from repro.serve import DONE, CAServeEngine, SimJob

    tag = f"[{kind}] serve {h}x{w}" + (f" mesh {dict(mesh.shape)}"
                                       if mesh is not None else "")
    with tempfile.TemporaryDirectory() as ckpt_dir:
        eng = CAServeEngine(height=h, width=w, slots=4, depth=4,
                            mesh=mesh, use_pallas=True, ckpt_dir=ckpt_dir,
                            ckpt_every=2)
        for rid in range(jobs):
            eng.submit(SimJob(rid=rid,
                              scenario=("cylinder", "bml_city")[rid % 2],
                              steps=steps, frame_every=steps // 4,
                              overrides={"seed": rid}))
        t0 = time.perf_counter()
        done = eng.drain()
        log(f"{tag}: drained {len(done)}/{jobs} jobs in "
            f"{time.perf_counter() - t0:.3f} s over {eng.stats['rounds']} "
            f"rounds (compiles included)")
        check(len(done) == jobs and all(j.status == DONE
                                        for j in eng.jobs.values()),
              f"{tag}: not every job is done")
        check(eng.stats["audits"] > 0 and eng.stats["audit_failures"] == 0,
              f"{tag}: audits {eng.stats['audits']}, failures "
              f"{eng.stats['audit_failures']}")
        ckpt = store.latest_valid_step(ckpt_dir)
        check(ckpt is not None, f"{tag}: no checkpoint landed")
        log(f"{tag}: audits={eng.stats['audits']} audit_failures=0 "
            f"latest checkpoint at round {ckpt}")
        for g in eng.groups.values():
            require_mosaic(g.run.lower(g.state, 0),
                           f"{tag} {g.variant} kernel step")

        checked = set()
        for job in sorted(eng.jobs.values(), key=lambda j: j.rid):
            sc = scenarios.get(job.scenario, height=h, width=w,
                               **job.overrides)
            if sc.variant in checked:
                continue
            checked.add(sc.variant)
            spec = sc.rule()
            check(len(job.segments) == 1, f"{tag}: job {job.rid} was split")
            t0_job = job.segments[0][0]
            ref = jax.jit(lambda p: rulespec.run_planes_rule(
                p, steps, spec, p_force=sc.p_force, t0=t0_job))(
                    sc.initial_planes())
            frame = dict(job.frames[steps])
            frame.pop("step")
            want = observables.frame_summary(ref, spec, frame["t"])
            check(frame == want, f"{tag}: job {job.rid} frame {frame} != "
                                 f"jnp stepper {want}")
            check(np.array_equal(job.result, np.asarray(ref)),
                  f"{tag}: job {job.rid} final state differs")
            log(f"{tag}: job {job.rid} ({sc.variant}) last frame equals "
                f"the jnp stepper's: {frame}")


def sharded_phase(kind: str, devices, h: int, w: int, depth: int,
                  steps: int, seed: int) -> None:
    """The halo-exchange stepper on a 2x2 mesh against the single-chip
    kernel on the same lattice."""
    import jax
    import jax.numpy as jnp

    from repro.core import distributed
    from repro.kernels.fhp_step import ops
    wd = w // 32
    p_force = 0.05
    mesh = distributed.make_mesh((2, 2), ("data", "model"),
                                 devices=devices)
    sharding = jax.sharding.NamedSharding(
        mesh, distributed.lattice_spec(("data",), "model", batched=True))
    planes = random_planes("fhp2", (1, h, wd), seed, sharding=sharding)
    tag = f"[{kind}] fhp2 {h}x{w} on a 2x2 mesh, depth {depth}"

    one = jax.device_put(planes, devices[0])
    bh, bw, T = ops.autotune_launch(h, wd)
    run1, _ = distributed.make_ensemble_run(
        None, steps, p_force=p_force, use_pallas=True, steps_per_launch=T,
        block_rows=bh, block_words=bw)
    lowered = jax.jit(run1).lower(one, 0)
    require_mosaic(lowered, f"[{kind}] single-chip reference kernel step")
    want, dt = timed(lowered.compile(), one, 0)
    log(f"[{kind}] single-chip kernel (tile {bh}x{bw}, T={T}): "
        f"{steps} steps in {dt:.6f} s")

    for overlap in (False, True):
        run, _ = distributed.make_ensemble_run(
            mesh, steps, p_force=p_force, depth=depth, use_pallas=True,
            steps_per_launch=depth, overlap=overlap)
        lowered = jax.jit(run).lower(planes, 0)
        require_mosaic(lowered, f"{tag} overlap={overlap} kernel step")
        t0 = time.perf_counter()
        step = lowered.compile()
        log(f"{tag} overlap={overlap}: compile "
            f"{time.perf_counter() - t0:.3f} s")
        out, dt = timed(step, planes, 0)
        log(f"{tag} overlap={overlap}: {steps} steps in {dt:.6f} s")
        shards = out.addressable_shards
        quarter = (1, 8, h // 2, wd // 2)
        check(len({s.device for s in shards}) == 4
              and len({str(s.index) for s in shards}) == 4
              and all(s.data.shape == quarter for s in shards),
              f"{tag}: shards {[(s.device, s.data.shape) for s in shards]}")
        log(f"{tag} overlap={overlap}: 4 distinct devices hold one "
            f"quarter {quarter} each: "
            f"{sorted(str(s.device) for s in shards)}")
        check(bool(jnp.array_equal(jax.device_put(out, devices[0]), want)),
              f"{tag} overlap={overlap}: differs from the single chip")
        log(f"{tag} overlap={overlap}: bit-identical to the single chip")
    drain_engine_phase(kind, 4096, 4096, jobs=4, steps=16, mesh=mesh)


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--chips", type=int, choices=(1, 4), default=1)
    args = ap.parse_args(argv)

    import jax
    devices = jax.devices()
    platform = devices[0].platform
    if platform != "tpu":
        print(f"chip_smoke: no TPU found (JAX sees {len(devices)} "
              f"{platform} device(s)); nothing was run", file=sys.stderr)
        return 1
    if len(devices) < args.chips:
        print(f"chip_smoke: --chips {args.chips} needs {args.chips} TPU "
              f"devices, JAX sees {len(devices)}", file=sys.stderr)
        return 1
    from repro.launch import compile_cache
    log(f"compile cache: {compile_cache.enable()}")
    kind = devices[0].device_kind
    log(f"devices: {len(devices)} x {kind}; jax {jax.__version__}")

    try:
        if args.chips == 4:
            sharded_phase(kind, devices[:4], 32768, 16384, depth=4,
                          steps=16, seed=3)
        else:
            large_lattice_phase(kind, "fhp2", 0.05, 16384, 16384,
                                steps=32, seed=1)
            large_lattice_phase(kind, "bml", 0.0, 16384, 16384,
                                steps=32, seed=2)
            drain_engine_phase(kind, 4096, 4096, jobs=8, steps=64)
    except SmokeFailure as e:
        print(f"chip_smoke: FAILED: {e}", file=sys.stderr)
        return 1
    print(json.dumps({"ok": True, "device": {
        "platform": platform, "kind": kind, "count": len(devices)}}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
