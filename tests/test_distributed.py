"""Distributed FHP == single-device reference (bit-exact), run in a
subprocess so the 8 fake host devices never leak into other tests."""
import os
import subprocess
import sys
import textwrap

import pytest

SCRIPT = textwrap.dedent("""
    import os
    os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
    import jax, jax.numpy as jnp
    from jax.sharding import NamedSharding
    from repro.core import byte_step, bitplane, distributed

    failures = []
    for mesh_shape, axes in [((4, 2), ("data", "model")),
                             ((2, 2, 2), ("pod", "data", "model"))]:
        mesh = distributed.make_mesh(mesh_shape, axes)
        y_axes = axes[:-1]
        H, W = 32, 256
        s = jnp.asarray(byte_step.make_channel(H, W, density=0.3, seed=3))
        p = bitplane.pack(s)
        sh = NamedSharding(mesh, distributed.lattice_spec(y_axes, "model"))
        pd = jax.device_put(p, sh)
        ref = bitplane.run_planes(p, 8, p_force=0.03)
        for depth in (1, 2, 4, 8):
            run = jax.jit(distributed.make_run(
                mesh, 8, y_axes=y_axes, x_axis="model",
                p_force=0.03, depth=depth))
            ok = bool((run(pd, 0) == ref).all())
            print(f"mesh={mesh_shape} depth={depth}: {ok}")
            if not ok:
                failures.append((mesh_shape, depth))
        rg = jax.jit(distributed.make_gspmd_run(
            mesh, 8, y_axes=y_axes, x_axis="model", p_force=0.03))
        ok = bool((rg(pd, 0) == ref).all())
        print(f"mesh={mesh_shape} gspmd: {ok}")
        if not ok:
            failures.append((mesh_shape, "gspmd"))
        rp = jax.jit(distributed.make_run(
            mesh, 8, y_axes=y_axes, x_axis="model", p_force=0.03,
            depth=1, use_pallas=True))
        ok = bool((rp(pd, 0) == ref).all())
        print(f"mesh={mesh_shape} pallas-local: {ok}")
        if not ok:
            failures.append((mesh_shape, "pallas"))
    assert not failures, failures
    print("ALL_OK")
""")


@pytest.mark.slow
def test_distributed_matches_single_device():
    r = subprocess.run([sys.executable, "-c", SCRIPT],
                       capture_output=True, text=True, timeout=600,
                       env=dict(os.environ, PYTHONPATH="src"))
    assert r.returncode == 0, r.stdout + r.stderr
    assert "ALL_OK" in r.stdout
