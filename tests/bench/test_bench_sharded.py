"""The sharded lattice cell (fhp2-flow-2x2.lattice) driven through whole
runs at a tiny size on four virtual CPU devices (the look for a chip
skipped): a sound run is correct, the control is not, and neither is a
run with the exchange between chips left out or a step that returns its
state unchanged.  The runs share one subprocess, which needs its own
device count."""
import json
import os
import subprocess
import sys

import pytest

ROOT = os.path.abspath(os.path.join(os.path.dirname(__file__), "..", ".."))

SCRIPT = r"""
import json, sys
sys.path[:0] = ["src", "."]
import jax, jax.numpy as jnp
from bench import harness
from repro.core import distributed
from repro.kernels.fhp_step import ops

# Interpret-mode compiles of deep temporal blocks take minutes on the
# CPU; the planner's choice is not what these runs are about.  Depth 2
# with the overlap split on: the sharded path with its boundary pieces.
ops.autotune_launch = lambda h, wd, **kw: (0, 0, 1, 2, True)
TINY = {"config": {"lattice": {"height": 64, "width": 4096,
                               "density": 0.22}},
        "traffic": {"steps_per_call": 4}}


def run(control=False):
    args = harness.parse(["--workload", "fhp2-flow-2x2.lattice", "--seed",
                          str(2 ** 33 + 11), "--seconds", "0.2"])
    res = harness.run_cell(args, require_tpu=False, overrides=TINY,
                           control=control)
    out = res.pop("_outcome")
    return {"correct": res["correct"], "count": res["device"]["count"],
            "checks": {c.name: c.value for c in out.checks},
            "control": out.control}


def local_halo(planes, d, ny, nx, y_axes, x_axis):
    # Each shard wraps onto itself: the exchange between chips left out.
    ext = jnp.concatenate([planes[..., -1:], planes, planes[..., :1]], -1)
    return jnp.concatenate([ext[..., -d:, :], ext, ext[..., :d, :]], -2)


real_run = distributed.make_ensemble_run


def unchanged(mesh, steps, **kw):
    inner, sharding = real_run(mesh, steps, **kw)
    return (lambda planes, t0: planes), sharding


results = {"sound": run(control=True)}
real_halo = distributed._exchange_halo
distributed._exchange_halo = local_halo
results["no_exchange"] = run()
distributed._exchange_halo = real_halo
distributed.make_ensemble_run = unchanged
results["unchanged"] = run()
print(json.dumps(results))
"""


@pytest.fixture(scope="module")
def runs():
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               XLA_FLAGS="--xla_force_host_platform_device_count=4")
    proc = subprocess.run([sys.executable, "-c", SCRIPT], cwd=ROOT, env=env,
                          capture_output=True, text=True, timeout=900)
    assert proc.returncode == 0, proc.stderr[-4000:]
    return json.loads(proc.stdout.strip().splitlines()[-1])


def test_sound_sharded_run_is_correct_and_control_is_not(runs):
    sound = runs["sound"]
    assert sound["count"] == 4
    assert sound["correct"], sound["checks"]
    assert sound["control"]["mismatched_words"] > 0


@pytest.mark.parametrize("fault", ["no_exchange", "unchanged"])
def test_broken_sharded_path_is_not_correct(runs, fault):
    assert not runs[fault]["correct"], runs[fault]["checks"]
