"""Paper Table 1 analogue: computational efficiency (Mups) per
implementation tier, measured wall-clock on this host.

Tier mapping (paper -> this repo):
  seq   -> byte-per-node stepper with LUT collisions (the paper's
           portable scalar algorithm, here already jnp-vectorised --
           so this baseline is *generous* vs true scalar C)
  SSE   -> byte-per-node stepper with branchless boolean collisions
           (vector boolean algebra at 1 node/lane)
  AVX   -> bit-plane (multi-spin) stepper: 32 nodes/word boolean algebra
  fused -> bit-plane with stream+collide fused in one pass (the Pallas
           kernel's algorithm; timed here via its jnp oracle equivalent
           because interpret-mode Pallas measures Python, not the kernel)

Mups = million lattice-site updates per second (paper's metric).
"""
from __future__ import annotations

import time

import jax
import jax.numpy as jnp

from repro.core import bitplane, byte_step

H, W = 512, 2048
STEPS = 10
P_FORCE = 0.01


def _time(fn, *args, reps=3):
    fn(*args).block_until_ready()  # compile + warm
    best = float("inf")
    for _ in range(reps):
        t0 = time.perf_counter()
        fn(*args).block_until_ready()
        best = min(best, time.perf_counter() - t0)
    return best


def mups(seconds: float) -> float:
    return H * W * STEPS / seconds / 1e6


def run() -> dict:
    state = jnp.asarray(byte_step.make_channel(H, W, density=0.3, seed=0))
    planes = bitplane.pack(state)

    @jax.jit
    def run_byte_lut(s):
        return byte_step.run_bytes(s, STEPS, p_force=P_FORCE)

    @jax.jit
    def run_byte_bool(s):
        # byte layout, boolean collisions (1 node/lane) = SSE analogue
        from repro.core import boolean, prng

        def step(s, t):
            s = byte_step.stream_bytes(s)
            pl = [(s >> i) & 1 for i in range(8)]
            chi = prng.random_bits((H, W), t, 1)
            out = boolean.collide_planes(pl, chi)
            s = sum((out[i].astype(jnp.uint8) << i) for i in range(8))
            acc = prng.bernoulli((H, W), t, P_FORCE)
            return byte_step.force_bytes(s, acc)

        return jax.lax.fori_loop(0, STEPS, lambda i, x: step(x, i), s)

    @jax.jit
    def run_bitplane(s):
        # unfused: stream pass then collide pass (2 memory sweeps)
        from repro.core import prng

        def step(p, t):
            p = bitplane.stream_planes(p)
            chi = prng.chirality_words((H, W // 32), t)
            p = bitplane.collide(p, chi)
            acc = prng.bernoulli_words((H, W // 32), t, P_FORCE)
            from repro.core import boolean
            return jnp.stack(boolean.force_planes(list(p), acc))

        return jax.lax.fori_loop(0, STEPS, lambda i, x: step(x, i), s)

    @jax.jit
    def run_bitplane_fused(s):
        return bitplane.run_planes(s, STEPS, p_force=P_FORCE)

    rows = {}
    rows["byte-LUT (seq analogue)"] = mups(_time(run_byte_lut, state))
    rows["byte-boolean (SSE analogue)"] = mups(_time(run_byte_bool, state))
    rows["bitplane (AVX analogue)"] = mups(_time(run_bitplane, planes))
    rows["bitplane-fused (kernel algo)"] = mups(_time(run_bitplane_fused,
                                                      planes))
    return rows


def main():
    rows = run()
    base = rows["byte-LUT (seq analogue)"]
    print("impl,mups,speedup_vs_seq")
    for name, v in rows.items():
        print(f"{name},{v:.1f},{v / base:.2f}")


if __name__ == "__main__":
    main()
