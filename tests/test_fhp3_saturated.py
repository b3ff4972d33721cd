"""FHP-III as published, collision-saturated: the class table, and every
path of the program against the plain reference the benchmark checks
with (``bench/reference_fhp3.py``), bit for bit, on seeded random states
with forcing and a solid disk.  Also the per-word operation count the
kernel's ``kernel.build`` event carries, pinned for the rules that draw
one random word or none."""
import collections
import functools
import json
import os
import subprocess
import sys

import jax
import jax.numpy as jnp
import pytest

ROOT = os.path.abspath(os.path.join(os.path.dirname(__file__), ".."))
sys.path[:0] = [ROOT]

from bench import reference, reference_fhp3  # noqa: E402
from repro import telemetry  # noqa: E402
from repro.core import bitplane, boolean, rules, rulespec  # noqa: E402
from repro.kernels.fhp_step import kernel as fhp_kernel  # noqa: E402
from repro.kernels.fhp_step.ops import fhp_step_pallas, run_pallas  # noqa: E402

H, W = 16, 256             # 8 packed words a row
P_FORCE = 0.2
FLUID = 1 << 7


def classes():
    by_key = collections.defaultdict(list)
    for s in range(FLUID):
        by_key[(rules.mass_of(s),) + rules.momentum_of(s)].append(s)
    return list(by_key.values())


# ---------------------------------------------------------------------------
# The class table
# ---------------------------------------------------------------------------

def test_classes_are_the_published_ones():
    sizes = collections.Counter(len(c) for c in classes() if len(c) > 1)
    assert sizes == {2: 12, 3: 14, 5: 2}
    assert len(rules.fhp3_table()) == 76
    assert sorted(map(tuple, classes())) == sorted(rules.fluid_classes())
    assert sorted(reference_fhp3.classes()) == sorted(
        c for c in rules.fluid_classes() if len(c) > 1)
    assert reference_fhp3.table() == rules.fhp3_table()


def test_every_collision_stays_in_its_class_uniformly():
    lut = rules.build_lut("fhp3")
    colliding = 0
    for cls in classes():
        for s in cls:
            outs = [int(lut[r, s]) for r in range(4)]
            if len(cls) == 1:
                assert outs == [s] * 4
                continue
            colliding += 1
            others = [o for o in cls if o != s]
            assert set(outs) == set(others), (s, outs)
            share = collections.Counter(outs)
            assert set(share.values()) == {4 // len(others)}, (s, share)
            for o in outs:
                assert rules.mass_of(o) == rules.mass_of(s)
                assert rules.momentum_of(o) == rules.momentum_of(s)
    assert colliding == 76


def test_solid_sites_and_force_as_fhp2():
    lut2, lut3 = rules.build_lut("fhp2"), rules.build_lut("fhp3")
    for s in range(FLUID, 256):
        assert set(lut3[:, s]) == {lut2[0, s]} == {rules.bounce_back(s)}
    assert rulespec.get_rule("fhp3").force is rulespec.get_rule("fhp2").force
    assert rulespec.get_rule("fhp3").force is boolean.force_planes


# ---------------------------------------------------------------------------
# Every path of the program against the plain reference
# ---------------------------------------------------------------------------

@pytest.fixture(scope="module")
def start():
    """Seeded random FHP fill with walls and a solid disk."""
    return jnp.asarray(reference.cylinder_planes(H, W, 0.3, seed=17))


@functools.partial(jax.jit, static_argnums=2)
def want(start, t0, steps):
    rows = jnp.arange(H, dtype=jnp.uint32)[:, None]
    return reference_fhp3.run_rows(start, t0, rows, steps, P_FORCE)


def test_reference_differs_from_fhp2(start):
    rows = jnp.arange(H, dtype=jnp.uint32)[:, None]
    a = reference_fhp3.run_rows(start, 3, rows, 2, P_FORCE)
    b = reference.run_rows(start, 3, rows, 2, "fhp2", P_FORCE)
    assert not bool((a == b).all())


@pytest.mark.parametrize("T,bw", [(1, 0), (1, 2), (2, 0), (2, 2)])
def test_kernel_matches_reference(start, T, bw):
    """One launch of the kernel in interpret mode, on the 1-D band (bw 0,
    the full width) and on an x-blocked tile, at T = 1 and T = 2."""
    got = fhp_step_pallas(start, 5, p_force=P_FORCE, steps_per_launch=T,
                          block_rows=8, block_words=bw, variant="fhp3")
    assert bool((got == want(start, 5, T)).all())


def test_run_pallas_matches_reference(start):
    """Three launches of one step through the single-chip run loop."""
    got = run_pallas(start, 3, p_force=P_FORCE, t0=9, steps_per_launch=1,
                     block_rows=8, variant="fhp3")
    assert bool((got == want(start, 9, 3)).all())


def test_kernel_with_precomputed_words_matches_reference(start):
    """T = 1 with the random words and forcing coin made outside the
    kernel and passed in: both words are passed."""
    got = fhp_step_pallas(start, 7, p_force=P_FORCE, rng_in_kernel=False,
                          block_rows=8, variant="fhp3")
    assert bool((got == want(start, 7, 1)).all())


def test_jnp_paths_match_reference(start):
    spec = rulespec.get_rule("fhp3")
    got = rulespec.run_planes_rule(start, 3, spec, P_FORCE, t0=2)
    assert bool((got == want(start, 2, 3)).all())
    cur = start
    for t in range(2, 5):
        cur = bitplane.step_planes(cur, t, P_FORCE, variant="fhp3")
    assert bool((cur == want(start, 2, 3)).all())


def test_byte_oracle_matches_reference(start):
    """The byte LUT driven by the word stream (``oracle_run``); the force
    pass after it, on the byte encoding, from the same coin."""
    from repro.core import byte_step, prng
    spec = rulespec.get_rule("fhp3")
    cur = bitplane.unpack(start)
    for t in range(4, 6):
        cur = rulespec.oracle_run(cur, 1, spec, t0=t)
        acc = prng.bernoulli_words((H, W // 32), t, P_FORCE)
        cur = byte_step.force_bytes(cur, bitplane.unpack(acc[None]) == 1)
    assert bool((bitplane.pack(cur) == want(start, 4, 2)).all())


SHARDED = r"""
import json, sys
sys.path[:0] = ["src", "."]
import jax, jax.numpy as jnp
from bench import reference, reference_fhp3
from repro.core import distributed

H, W, STEPS, P = 32, 512, 2, 0.2
start = jnp.asarray(reference.cylinder_planes(H, W, 0.3, seed=5))
rows = jnp.arange(H, dtype=jnp.uint32)[:, None]
want = reference_fhp3.run_rows(start, 3, rows, STEPS, P)
mesh = distributed.make_mesh((2, 2), ("data", "model"))
out = {"devices": len(jax.devices())}
for overlap in (False, True):
    run, sharding = distributed.make_ensemble_run(
        mesh, STEPS, variant="fhp3", p_force=P, depth=2, use_pallas=True,
        steps_per_launch=1, overlap=overlap)
    got = run(jax.device_put(start[None], sharding), 3)[0]
    out[str(overlap)] = int(jnp.sum(got != want))
print(json.dumps(out))
"""


def test_sharded_2x2_matches_reference():
    """The sharded stepper on a 2x2 mesh of virtual CPU devices (one
    round of halo depth 2, a step a launch), with and without the overlap
    split."""
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               XLA_FLAGS="--xla_force_host_platform_device_count=4")
    proc = subprocess.run([sys.executable, "-c", SHARDED], cwd=ROOT, env=env,
                          capture_output=True, text=True, timeout=900)
    assert proc.returncode == 0, proc.stderr[-4000:]
    out = json.loads(proc.stdout.strip().splitlines()[-1])
    assert out == {"devices": 4, "False": 0, "True": 0}, out


# ---------------------------------------------------------------------------
# Operations per word-step and the kernel's build event
# ---------------------------------------------------------------------------

# Counted by the same function on the tree before FHP-III drew its second
# word: the one-word and RNG-free rules' kernels are unchanged.
PINNED = [("fhp2", 0.03, 698), ("fhp2", 0.05, 711), ("fhp2", 0.0, 448),
          ("bml", 0.0, 74)]


@pytest.mark.parametrize("name,p,ops", PINNED)
def test_ops_per_word_step_pinned(name, p, ops):
    assert rulespec.ops_per_word_step(rulespec.get_rule(name), p) == ops


def test_ops_per_word_step_counts_the_second_word():
    """FHP-III's count is FHP-II's plus the larger circuit and one more
    hash: both words are drawn, the whole circuit is counted."""
    from repro.core import prng
    word = jax.ShapeDtypeStruct((8, 128), jnp.uint32)

    def eqns(fn, *shapes):
        return len(jax.make_jaxpr(fn)(*shapes).eqns)

    collide2 = eqns(lambda *p: boolean.collide_planes(p[:8], p[8], "fhp2"),
                    *[word] * 9)
    collide3 = eqns(lambda *p: boolean.collide_planes(p[:8], p[8:], "fhp3"),
                    *[word] * 10)
    hash_ = eqns(lambda r, c, t: prng.word_u32_at(r, c, t, salt=0x33),
                 jax.ShapeDtypeStruct((8, 1), jnp.uint32),
                 jax.ShapeDtypeStruct((1, 128), jnp.uint32),
                 jax.ShapeDtypeStruct((), jnp.uint32))
    for p in (0.0, 0.03):
        assert (rulespec.ops_per_word_step(rulespec.get_rule("fhp3"), p)
                - rulespec.ops_per_word_step(rulespec.get_rule("fhp2"), p)
                == collide3 - collide2 + hash_)


def _build(variant, pq):
    return fhp_kernel.make_fhp_step(16, 8, bh=8, bw=4, pq=pq, steps=2,
                                    rng_in_kernel=True, interpret=True,
                                    variant=variant)


def test_kernel_build_event(monkeypatch):
    tel = telemetry.default()
    monkeypatch.setattr(tel, "enabled", True)
    seen = len(tel.events())
    _build("fhp3", 1966)
    (event,) = [e for e in tel.events()[seen:] if e["name"] == "kernel.build"]
    assert event["attrs"] == {
        "rule": "fhp3", "random_words": 2,
        "ops_per_word_step": rulespec.ops_per_word_step(
            rulespec.get_rule("fhp3"), 1966 / 65536),
        "block_rows": 8, "block_words": 4, "steps_per_launch": 2}


def test_kernel_build_costs_nothing_with_telemetry_off(monkeypatch):
    tel = telemetry.default()
    monkeypatch.setattr(tel, "enabled", False)

    def counted(*a, **kw):
        raise AssertionError("counted with telemetry off")

    monkeypatch.setattr(rulespec, "ops_per_word_step", counted)
    seen = len(tel.events())
    _build("fhp2", 1966)
    assert len(tel.events()) == seen
