"""Distributed FHP demo: the production domain decomposition running on 8
fake host devices, verified bit-identical to the single-device stepper,
with halo-widening depth sweep and the static-geometry cache (an obstacle
scenario exchanging 7 dynamic planes per round).

Run from the repo root with the package on PYTHONPATH (no path hacks):

    PYTHONPATH=src python examples/fhp_distributed.py
"""
import os

os.environ.setdefault("XLA_FLAGS", "--xla_force_host_platform_device_count=8")

import time  # noqa: E402

import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402
from jax.sharding import NamedSharding  # noqa: E402

from repro import scenarios  # noqa: E402
from repro.core import bitplane, byte_step, distributed  # noqa: E402


def main():
    mesh = distributed.make_mesh((2, 2, 2), ("pod", "data", "model"))
    print(f"mesh: {dict(zip(mesh.axis_names, mesh.devices.shape))}")
    H, W, steps = 128, 1024, 16
    planes = bitplane.pack(jnp.asarray(
        byte_step.make_channel(H, W, density=0.25, seed=0)))
    sh = NamedSharding(mesh, distributed.lattice_spec(("pod", "data"),
                                                      "model"))
    pd = jax.device_put(planes, sh)
    ref = bitplane.run_planes(planes, steps, p_force=0.02)

    for depth in (1, 2, 4, 8):
        run = jax.jit(distributed.make_run(
            mesh, steps, y_axes=("pod", "data"), x_axis="model",
            p_force=0.02, depth=depth))
        out = run(pd, 0)
        out.block_until_ready()
        t0 = time.perf_counter()
        out = run(pd, 0).block_until_ready()
        dt = time.perf_counter() - t0
        exact = bool((out == ref).all())
        print(f"depth={depth}: bit-identical={exact}  "
              f"({H * W * steps / dt / 1e6:.1f} Mups on 8 host devices; "
              f"{steps // depth} halo exchanges)")
        assert exact

    # Static-geometry cache: an obstacle scenario through the fused
    # extended path -- the solid apron is exchanged once, every round
    # moves 7 dynamic planes instead of 8.
    sc = scenarios.get("cylinder", height=H, width=W)
    planes = sc.initial_planes()
    pd = jax.device_put(planes, sh)
    ref = bitplane.run_planes(planes, steps, p_force=sc.p_force)
    run = jax.jit(distributed.make_run(
        mesh, steps, y_axes=("pod", "data"), x_axis="model",
        p_force=sc.p_force, depth=4, use_pallas=True, steps_per_launch=2,
        static_solid=True))
    exact = bool((run(pd, 0) == ref).all())
    print(f"cylinder scenario, static-geometry cache, depth=4: "
          f"bit-identical={exact} (7/8 exchange bytes per round)")
    assert exact
    print("OK: domain decomposition is bit-exact at every halo depth")


if __name__ == "__main__":
    main()
