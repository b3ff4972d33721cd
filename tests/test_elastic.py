"""Elastic re-scale: a checkpoint written under one mesh restores onto a
different mesh (different DP extent) and training continues with
identical results — the restart path for losing/gaining nodes."""
import os
import subprocess
import sys
import textwrap

import pytest

SCRIPT = textwrap.dedent("""
    import os, tempfile
    os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
    import jax, jax.numpy as jnp, numpy as np
    from jax.sharding import NamedSharding, PartitionSpec as P
    from repro import checkpoint as ckpt
    from repro.configs import get_smoke
    from repro.core.distributed import make_mesh
    from repro.data.pipeline import SyntheticLM
    from repro.models import init_params, loss_fn
    from repro.optim import AdamW, cosine_schedule
    from repro.parallel import tree_shardings
    from repro.train import make_train_step

    cfg = get_smoke("repro-100m")
    opt = AdamW(lr=cosine_schedule(1e-3, 2, 10))
    data = SyntheticLM(vocab=cfg.vocab, seq_len=32, global_batch=8, seed=0)
    step = make_train_step(cfg, opt)

    def run_steps(mesh, params, opt_state, t0, n):
        shard = tree_shardings(mesh, params, axes)
        params = jax.tree.map(jax.device_put, params, shard)
        fn = jax.jit(step)
        with mesh:
            for s in range(t0, t0 + n):
                b = {k: jnp.asarray(v) for k, v in data.batch_at(s).items()}
                params, opt_state, m = fn(params, opt_state, b)
        return params, opt_state, float(m["loss"])

    params, axes = init_params(cfg, jax.random.key(0))
    opt_state = opt.init(params)

    # mesh A: 4-way data x 2-way model
    mesh_a = make_mesh((4, 2), ("data", "model"))
    pa, oa, _ = run_steps(mesh_a, params, opt_state, 0, 2)
    with tempfile.TemporaryDirectory() as d:
        ckpt.save(d, 2, {"params": pa, "opt": oa})

        # "cluster shrinks": mesh B is 2-way data x 4-way model
        mesh_b = make_mesh((2, 4), ("data", "model"))
        shard_b = {"params": tree_shardings(mesh_b, pa, axes),
                   "opt": {"m": tree_shardings(mesh_b, pa, axes),
                           "v": tree_shardings(mesh_b, pa, axes),
                           "step": None}}
        restored = ckpt.restore(d, 2, {"params": pa, "opt": oa}, shard_b)
        pb, ob = restored["params"], restored["opt"]
        # restored arrays live on mesh B
        sh = jax.tree.leaves(pb)[0].sharding
        assert sh.mesh.devices.shape == (2, 4), sh

        # continue 2 steps on each mesh: identical losses & params
        pa2, oa2, la = run_steps(mesh_a, pa, oa, 2, 2)
        pb2, ob2, lb = run_steps(mesh_b, pb, ob, 2, 2)
        assert abs(la - lb) < 1e-5, (la, lb)
        for x, y in zip(jax.tree.leaves(pa2), jax.tree.leaves(pb2)):
            np.testing.assert_allclose(np.asarray(x), np.asarray(y),
                                       rtol=1e-5, atol=1e-6)
    print("ELASTIC_OK")
""")


@pytest.mark.slow
def test_elastic_mesh_rescale():
    r = subprocess.run([sys.executable, "-c", SCRIPT],
                       capture_output=True, text=True, timeout=900,
                       env=dict(os.environ, PYTHONPATH="src"))
    assert r.returncode == 0, r.stdout[-3000:] + r.stderr[-3000:]
    assert "ELASTIC_OK" in r.stdout


CA_SCRIPT = textwrap.dedent("""
    import os, tempfile
    os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
    import jax, jax.numpy as jnp, numpy as np
    from jax.sharding import NamedSharding
    from repro import checkpoint as ckpt
    from repro.core import bitplane, distributed, rulespec

    name, H, W = "fhp3", 32, 256
    spec = rulespec.get_rule(name)
    planes = bitplane.pack(jnp.asarray(spec.init_bytes(H, W, 0.3, 9)),
                           n_planes=spec.n_planes)

    def run_on(mesh, p, t0, steps, variant):
        sh = NamedSharding(mesh, distributed.lattice_spec(("data",), "model"))
        run = jax.jit(distributed.make_run(
            mesh, steps, y_axes=("data",), x_axis="model", depth=2,
            use_pallas=True, steps_per_launch=2, variant=variant))
        return run(jax.device_put(p, sh), t0)

    # mesh A advances to t=4, checkpoints with the rule name in metadata
    mesh_a = distributed.make_mesh((4, 2), ("data", "model"))
    mid = run_on(mesh_a, planes, 0, 4, name)
    with tempfile.TemporaryDirectory() as d:
        ckpt.save(d, 4, {"planes": mid}, meta={"rule": name, "t": 4})
        step = ckpt.latest_step(d)
        meta = ckpt.load_meta(d, step)
        assert meta == {"rule": name, "t": 4}, meta

        # "cluster reshapes": restore onto a 2x4 mesh, continue under the
        # rule named by the checkpoint
        mesh_b = distributed.make_mesh((2, 4), ("data", "model"))
        sh_b = NamedSharding(mesh_b,
                             distributed.lattice_spec(("data",), "model"))
        restored = ckpt.restore(d, step, {"planes": mid}, {"planes": sh_b})
        pb = restored["planes"]
        assert pb.sharding.mesh.devices.shape == (2, 4)
        out = run_on(mesh_b, pb, meta["t"], 4, meta["rule"])

    # == 8 uninterrupted single-device steps, bit-exact
    want = rulespec.run_planes_rule(planes, 8, spec)
    assert bool((out == want).all())
    print("CA_ELASTIC_OK")
""")


@pytest.mark.slow
def test_ca_checkpoint_rule_roundtrip():
    """A CA checkpoint carries its rule name in the manifest metadata, so
    a restarted ensemble replays bit-exactly under the right rule even
    after an elastic mesh reshape (counter-based RNG: resume at the saved
    ``t`` reproduces the uninterrupted stream)."""
    r = subprocess.run([sys.executable, "-c", CA_SCRIPT],
                       capture_output=True, text=True, timeout=900,
                       env=dict(os.environ, PYTHONPATH="src"))
    assert r.returncode == 0, r.stdout[-3000:] + r.stderr[-3000:]
    assert "CA_ELASTIC_OK" in r.stdout


CA_CORRUPT_SCRIPT = textwrap.dedent("""
    import os, tempfile
    os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
    import jax, jax.numpy as jnp, numpy as np
    from jax.sharding import NamedSharding
    from repro import checkpoint as ckpt
    from repro.core import bitplane, distributed, rulespec

    name, H, W = "fhp3", 32, 256
    spec = rulespec.get_rule(name)
    planes = bitplane.pack(jnp.asarray(spec.init_bytes(H, W, 0.3, 9)),
                           n_planes=spec.n_planes)
    mesh = distributed.make_mesh((4, 2), ("data", "model"))
    sh = NamedSharding(mesh, distributed.lattice_spec(("data",), "model"))
    run = jax.jit(distributed.make_run(
        mesh, 4, y_axes=("data",), x_axis="model", depth=2,
        use_pallas=True, steps_per_launch=2, variant=name))

    with tempfile.TemporaryDirectory() as d:
        state = jax.device_put(planes, sh)
        # Checkpoint every 4 steps up to t=12.
        for t in (0, 4, 8):
            ckpt.save(d, t + 4, {"planes": run(state, t)},
                      meta={"rule": name, "t": t + 4})
            state = run(state, t)
        assert ckpt.latest_step(d) == 12

        # The newest checkpoint is torn (truncated leaf), the one before
        # it has a garbled payload byte: the restart anchor must fall
        # back to t=4 via the checksum walk.
        p12 = ckpt.store.step_dir(d, 12)
        leaf = [f for f in os.listdir(p12) if f.endswith(".npy")][0]
        fp = os.path.join(p12, leaf)
        with open(fp, "r+b") as fh:
            fh.truncate(os.path.getsize(fp) // 2)
        p8 = ckpt.store.step_dir(d, 8)
        leaf = [f for f in os.listdir(p8) if f.endswith(".npy")][0]
        fp = os.path.join(p8, leaf)
        raw = bytearray(open(fp, "rb").read()); raw[-1] ^= 0xAA
        open(fp, "wb").write(bytes(raw))

        anchor = ckpt.latest_valid_step(d)
        assert anchor == 4, anchor
        meta = ckpt.load_meta(d, anchor)
        restored = ckpt.restore(d, anchor, {"planes": planes},
                                {"planes": sh})
        out = restored["planes"]
        # Replay 12 - 4 = 8 steps from the anchor: bit-exact catch-up.
        for t in range(meta["t"], 12, 4):
            out = run(out, t)

    want = rulespec.run_planes_rule(planes, 12, spec)
    assert bool((np.asarray(out) == np.asarray(want)).all())
    print("CA_CORRUPT_FALLBACK_OK")
""")


@pytest.mark.slow
def test_ca_corrupted_checkpoint_fallback_replay():
    """Disk corruption on the restart path: the newest checkpoint is
    torn and the next is checksum-garbled, so ``latest_valid_step``
    falls back two intervals, and the sharded fhp3 replay from that
    anchor is bit-exact with the uninterrupted run (counter-based RNG:
    replaying [t_anchor, t) reproduces the identical stream)."""
    r = subprocess.run([sys.executable, "-c", CA_CORRUPT_SCRIPT],
                       capture_output=True, text=True, timeout=900,
                       env=dict(os.environ, PYTHONPATH="src"))
    assert r.returncode == 0, r.stdout[-3000:] + r.stderr[-3000:]
    assert "CA_CORRUPT_FALLBACK_OK" in r.stdout
