"""Scenario suite through the sharded extended Pallas path: every
registered scenario runs on a 2x2 fake-device mesh under its own rule
(``Scenario.variant`` -> ``core.rulespec``), is checked bit-exact
against the single-device reference and conservation-audited, and emits
per-scenario records into BENCH_kernel.json.

FHP scenarios use the static-geometry cache (7 dynamic planes per
exchange, solid apron exchanged once; the modeled static-vs-dynamic
columns show the ~12.5% exchange cut).  Rules without a solid plane
(``bml_city``) take the dynamic path with per-species car-count
conservation and the jam-fraction order parameter in the record --
2-plane BML also demonstrates the per-rule bytes/site scaling of the
traffic model (``n_planes``).

Wall clock is only meaningful on a real multi-chip backend (CPU runs the
kernel in interpret mode); the durable outputs are the bit-exactness /
mass assertions (this is the CI scenario smoke sweep) and the model
columns.  The sweep, model records included, runs in a child process:
the fake-device XLA_FLAGS never leak into the parent, and the parent
never touches JAX, so on a TPU host the child can hold the chips.

    PYTHONPATH=src python -m benchmarks.bench_scenarios          # full
    PYTHONPATH=src python -m benchmarks.bench_scenarios --smoke  # tiny/CI
"""
from __future__ import annotations

import json
import os
import subprocess
import sys
import textwrap
from typing import Dict, List

MESH = (2, 2)

SCRIPT = textwrap.dedent("""
    import os, sys
    os.environ["XLA_FLAGS"] = (os.environ.get("XLA_FLAGS", "") +
                               " --xla_force_host_platform_device_count=4")
    import json, time
    import jax, jax.numpy as jnp
    from jax.sharding import NamedSharding
    from repro import scenarios
    from repro.core import bitplane, distributed, rulespec
    from repro.geometry import raster
    from repro.kernels.fhp_step.ops import pick_block_rows_extended
    from repro.roofline.analysis import sharded_fhp_traffic
    from repro.scenarios import observables

    from benchmarks.bench_scenarios import _model_records

    smoke = sys.argv[1] == "smoke"
    for rec in _model_records(smoke):
        print("RECORD " + json.dumps(rec))
    h, w = (32, 256) if smoke else (64, 1024)
    steps, depth, T = (8, 4, 2) if smoke else (16, 8, 4)
    mesh = distributed.make_mesh((2, 2), ("data", "model"))
    hl, wdl = h // 2, w // 32 // 2
    sh = NamedSharding(mesh, distributed.lattice_spec(("data",), "model"))
    bh = pick_block_rows_extended(wdl + 2, steps=T)

    for name in scenarios.names():
        sc = scenarios.get(name, height=h, width=w)
        spec = sc.rule()
        static = spec.solid_plane is not None
        planes = sc.initial_planes()
        ref = rulespec.run_planes_rule(planes, steps, spec,
                                       p_force=sc.p_force)
        pd = jax.device_put(planes, sh)
        run = jax.jit(distributed.make_run(
            mesh, steps, y_axes=("data",), x_axis="model",
            p_force=sc.p_force, depth=depth, use_pallas=True,
            steps_per_launch=T, static_solid=static, variant=sc.variant))
        out = run(pd, 0)
        out.block_until_ready()
        t0 = time.perf_counter()
        out = run(pd, 0)
        out.block_until_ready()
        dt = time.perf_counter() - t0
        exact = bool((out == ref).all())
        impl = "pallas-sharded-static" if static else "pallas-sharded"
        assert exact, f"{name}: sharded {impl} path diverged from reference"

        def counts(p):
            return [int(jax.lax.population_count(p[i]).sum())
                    for i in spec.mass_planes]

        c0, c1 = counts(planes), counts(out)
        conserved = (c0 == c1 if spec.per_plane_conserved
                     else sum(c0) == sum(c1))
        assert conserved, f"{name}: mass not conserved ({c0} -> {c1})"
        drag = {}
        for n, g in sc.obstacles:
            words = jnp.asarray(raster.solid_words(g, (h, w // 32)))
            px2, py = observables.solid_momentum(out, words)
            drag[n] = [int(px2), int(py)]
        m = sharded_fhp_traffic(hl, wdl, depth=depth, T=T, block_rows=bh,
                                static_solid=static,
                                n_planes=spec.n_planes)
        m8 = sharded_fhp_traffic(hl, wdl, depth=depth, T=T, block_rows=bh,
                                 static_solid=False,
                                 n_planes=spec.n_planes)
        rec = {"bench": "scenarios", "impl": impl,
               "backend": jax.default_backend(), "mesh": [2, 2],
               "scenario": name, "rule": sc.variant,
               "n_planes": spec.n_planes, "depth": depth, "T": T, "B": 1,
               "steps": steps, "lattice": [h, w], "smoke": smoke,
               "structural": False, "static_solid": static,
               "bit_exact": exact, "mass_conserved": conserved,
               "sites_per_sec": h * w * steps / dt,
               "obstacle_momentum": drag,
               "block_rows": bh,
               "model_hbm_bytes_per_site": m["hbm_bytes_per_site_step"],
               "model_ici_bytes_per_site": m["ici_bytes_per_site_step"],
               "model_ici_bytes_per_site_dynamic_geometry":
                   m8["ici_bytes_per_site_step"],
               "model_exchange_bytes_cut":
                   1.0 - m["ici_bytes_per_site_step"]
                       / m8["ici_bytes_per_site_step"],
               "model_exchanges_per_step": m["exchanges_per_step"],
               "model_launches_per_step": m["launches_per_step"]}
        if static:
            rec["solid_sites"] = int(jnp.sum(jax.lax.population_count(
                planes[spec.solid_plane])))
        else:
            rec["jam_fraction"] = float(observables.jam_fraction(out, steps))
            rec["car_counts"] = c1
        print("RECORD " + json.dumps(rec))
    print("BENCH_DONE")
""")


def _model_records(smoke: bool) -> List[Dict]:
    """Structural records: the static-vs-dynamic exchange model at the
    autotuned sharded point for representative shard sizes (no mesh, no
    timing)."""
    from repro.kernels.fhp_step.ops import autotune_launch
    from repro.roofline.analysis import sharded_fhp_traffic
    shards = [(256, 32)] if smoke else [(256, 32), (1024, 128)]
    out = []
    for hl, wdl in shards:
        bh, bw, T, depth, _overlap = autotune_launch(hl, wdl, max_depth=16,
                                                     static_solid=True)
        for static in (False, True):
            m = sharded_fhp_traffic(hl, wdl, depth=depth, T=T,
                                    block_rows=bh, block_words=bw,
                                    static_solid=static)
            out.append({
                "bench": "scenarios",
                "impl": "pallas-sharded-static" if static
                        else "pallas-sharded",
                "backend": None, "shard": [hl, wdl], "block_rows": bh,
                "block_words": bw,
                "T": T, "depth": depth, "B": 1, "sites_per_sec": None,
                "lattice": None, "smoke": smoke, "structural": True,
                "autotuned": True, "static_solid": static,
                "model_hbm_bytes_per_site": m["hbm_bytes_per_site_step"],
                "model_ici_bytes_per_site": m["ici_bytes_per_site_step"],
                "model_ici_bytes_per_exchange": m["ici_bytes_per_exchange"],
                "model_geometry_exchange_bytes":
                    m["geometry_exchange_bytes"],
                "model_exchanges_per_step": m["exchanges_per_step"],
                "model_launches_per_step": m["launches_per_step"]})
    return out


def main(smoke: bool = False) -> List[Dict]:
    """Run the sweep in a child process and collect its records.  This
    process never touches JAX: on a TPU host the child is the one
    process that holds the chips."""
    env = dict(os.environ)
    env["PYTHONPATH"] = "src" + os.pathsep + env.get("PYTHONPATH", "")
    r = subprocess.run(
        [sys.executable, "-c", SCRIPT, "smoke" if smoke else "full"],
        capture_output=True, text=True, timeout=1800, env=env)
    if r.returncode != 0 or "BENCH_DONE" not in r.stdout:
        # Fail loudly (never-empty-trajectory guarantee, and this sweep
        # doubles as the CI scenario smoke gate).
        raise RuntimeError("bench_scenarios subprocess failed:\n"
                           f"{r.stdout}\n{r.stderr}")
    records = []
    for line in r.stdout.splitlines():
        if not line.startswith("RECORD "):
            continue
        rec = json.loads(line[len("RECORD "):])
        records.append(rec)
        if rec["structural"]:
            tag = "static" if rec["static_solid"] else "dynamic"
            print(f"model_ici_bytes_per_site(shard={rec['shard']},{tag}),"
                  f"{rec['model_ici_bytes_per_site']:.4f},B")
        else:
            print(f"{rec['scenario']}_sps,{rec['sites_per_sec']:.3e},"
                  f"sites/s (exact={rec['bit_exact']})")
    return records


if __name__ == "__main__":
    main(smoke="--smoke" in sys.argv[1:])
