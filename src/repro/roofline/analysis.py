"""Roofline accounting from compiled XLA artifacts (no hardware needed).

Per (arch x shape x mesh) cell, three terms in *seconds per step*:

    compute    = HLO_FLOPs_per_device / peak_FLOPs_per_chip
    memory     = HLO_bytes_per_device / HBM_bandwidth_per_chip
    collective = collective_bytes_per_device / ICI_link_bandwidth

``compiled.cost_analysis()`` provides per-device FLOPs and bytes (the SPMD
partitioner has already divided the global program).  Collective bytes are
not in cost_analysis: we parse the *post-partitioning* HLO text
(``compiled.as_text()``) and sum operand sizes of every all-gather /
all-reduce / reduce-scatter / all-to-all / collective-permute, per the
assignment spec.  A wire-bytes estimate (ring-algorithm factors) is also
reported for context.

Hardware constants (assignment-fixed, TPU v5e): 197 TFLOP/s bf16,
819 GB/s HBM, ~50 GB/s/link ICI.
"""
from __future__ import annotations

import dataclasses
import re
from typing import Dict, Optional

import numpy as np


@dataclasses.dataclass(frozen=True)
class HW:
    peak_flops: float = 197e12      # bf16 / chip
    hbm_bw: float = 819e9           # B/s per chip
    ici_bw: float = 50e9            # B/s per link


V5E = HW()

_DTYPE_BYTES = {
    "f64": 8, "s64": 8, "u64": 8, "c64": 8,
    "f32": 4, "s32": 4, "u32": 4,
    "bf16": 2, "f16": 2, "s16": 2, "u16": 2,
    "s8": 1, "u8": 1, "pred": 1,
    "f8e4m3fn": 1, "f8e5m2": 1, "f8e4m3": 1,
}

_COLL_OPS = ("all-gather", "all-reduce", "reduce-scatter", "all-to-all",
             "collective-permute")

# ``bf16[8,128]{1,0}`` or ``f32[]`` (scalars); captures (dtype, dims).
_SHAPE_RE = re.compile(r"\b(" + "|".join(_DTYPE_BYTES) + r")\[([0-9,]*)\]")
# ``<result shapes> opcode(`` with optional -start/-done async suffixes.
_OP_RE = re.compile(
    r"=\s*(.*?)\b(" + "|".join(_COLL_OPS) + r")(-start|-done)?\(")


def _shape_bytes(dtype: str, dims: str) -> int:
    n = 1
    for d in dims.split(","):
        if d:
            n *= int(d)
    return n * _DTYPE_BYTES[dtype]


def _group_size(line: str) -> int:
    """Participant count of the op's replica groups (both HLO formats)."""
    m = re.search(r"replica_groups=\[(\d+),(\d+)\]<=", line)
    if m:
        return int(m.group(2))
    m = re.search(r"replica_groups=\{\{([0-9,]+)\}", line)
    if m:
        return len(m.group(1).split(","))
    m = re.search(r"source_target_pairs=\{(.+?)\}\}?", line)
    if m:  # collective-permute: pairwise
        return 2
    return 2


def collective_bytes(hlo_text: str) -> Dict[str, Dict[str, float]]:
    """Sum operand bytes per collective op kind from partitioned HLO text.

    The partitioned dialect prints only *result* shapes inline; operand
    bytes are derived from the result shape and op semantics:
    all-reduce / all-to-all / collective-permute keep shape, all-gather's
    operand is result/n, reduce-scatter's operand is result*n (n = replica
    group size).  Returns {op: {count, operand_bytes, wire_bytes}} plus a
    "_total" entry; wire_bytes uses ring-algorithm factors.
    """
    out: Dict[str, Dict[str, float]] = {
        op: {"count": 0, "operand_bytes": 0.0, "wire_bytes": 0.0}
        for op in _COLL_OPS}
    for line in hlo_text.splitlines():
        m = _OP_RE.search(line)
        if not m:
            continue
        result_seg, op, suffix = m.group(1), m.group(2), m.group(3)
        if suffix == "-done":        # async pair: count the -start only
            continue
        shapes = _SHAPE_RE.findall(result_seg)
        rb = sum(_shape_bytes(dt, dims) for dt, dims in shapes)
        n = _group_size(line)
        operand = {"all-reduce": rb,
                   "all-gather": rb / max(n, 1),
                   "reduce-scatter": rb * n,
                   "all-to-all": rb,
                   "collective-permute": rb}[op]
        wire = {"all-reduce": 2 * (n - 1) / n * rb,
                "all-gather": (n - 1) / n * rb,
                "reduce-scatter": (n - 1) / n * rb * n,
                "all-to-all": (n - 1) / n * rb,
                "collective-permute": rb}[op]
        out[op]["count"] += 1
        out[op]["operand_bytes"] += operand
        out[op]["wire_bytes"] += wire
    out["_total"] = {
        "count": sum(v["count"] for v in out.values()),
        "operand_bytes": sum(v["operand_bytes"] for v in out.values()),
        "wire_bytes": sum(v["wire_bytes"] for v in out.values()),
    }
    return out


# ---------------------------------------------------------------------------
# Fusion-aware HBM traffic estimate.
#
# XLA's cost_analysis "bytes accessed" sums operand+output bytes of EVERY
# HLO op pre-fusion -- a long elementwise chain that executes as one fused
# kernel pass is counted once per op, inflating traffic by 1-2 orders of
# magnitude.  The optimized module text, however, shows the post-fusion
# instruction graph: fusion internals live in separate computation blocks
# referenced by ``calls=``/``to_apply=``.  Summing output + operand bytes
# over *top-level* instructions only (entry, while bodies, conditionals)
# approximates real HBM traffic: each materialised buffer is written once
# by its producer and read once per consumer.
# ---------------------------------------------------------------------------

_DEF_RE = re.compile(r"^\s*(?:ROOT\s+)?%([\w.-]+)\s*=\s*(.*)$")
_NAME_RE = re.compile(r"%([\w.-]+)")
_SKIP_OPS = ("parameter", "constant", "tuple", "get-tuple-element",
             "bitcast", "after-all", "partition-id", "replica-id", "iota",
             "copy-start", "copy-done")


def _computation_blocks(text: str):
    """Yield (name, [lines]) per computation block in an HLO dump.

    Headers look like ``%name (p0: f32[..]) -> f32[..] {`` or
    ``ENTRY %main.0 (...) -> ... {``."""
    name, lines = None, []
    for line in text.splitlines():
        stripped = line.strip()
        if (name is None and stripped.endswith("{")
                and ("->" in stripped or stripped.startswith("ENTRY"))):
            m = re.match(r"^(?:ENTRY\s+)?%([\w.$-]+)", stripped)
            if m:
                name, lines = m.group(1), []
            continue
        if stripped == "}" and name is not None:
            yield name, lines
            name, lines = None, []
        elif name is not None:
            lines.append(line)


# Ops that materialise HBM buffers even under the TPU fusion pipeline.
_MAJOR_OPS = ("dot", "convolution", "fusion", "custom-call",
              "all-gather", "all-reduce", "reduce-scatter", "all-to-all",
              "collective-permute", "dynamic-slice", "dynamic-update-slice",
              "gather", "scatter", "concatenate", "pad", "sort", "copy",
              "transpose", "while", "reduce ", "reduce(")


def hbm_bytes_estimate(hlo_text: str, mode: str = "fused") -> float:
    """HBM traffic estimate (bytes) from optimized HLO text.

    mode="all": every top-level instruction's output + operand bytes --
    matches XLA's own pre-fusion accounting on the CPU pipeline (an UPPER
    bound for TPU: the CPU pipeline materialises elementwise chains that
    the TPU fusion pipeline keeps in registers/VMEM).

    mode="fused": models perfect elementwise fusion -- 2x (write + read)
    the bytes of buffers that *must* materialise: computation parameters,
    roots, and major ops (dot / collectives / gather / scatter / dynamic
    slicing / concatenate / sort / transpose).  A LOWER bound for TPU.
    The true TPU number lies between the two; EXPERIMENTS.md reports both.
    """
    fused = set(re.findall(r"(?:calls|to_apply)=%([\w.-]+)", hlo_text))
    shapes: Dict[str, float] = {}
    blocks = list(_computation_blocks(hlo_text))
    for _, lines in blocks:
        for line in lines:
            m = _DEF_RE.match(line)
            if not m:
                continue
            head = m.group(2).split("(", 1)[0]
            sh = _SHAPE_RE.findall(head)
            shapes[m.group(1)] = sum(_shape_bytes(d, s) for d, s in sh)

    total = 0.0
    for cname, lines in blocks:
        if cname in fused:
            continue
        for line in lines:
            m = _DEF_RE.match(line)
            if not m:
                continue
            rest = m.group(2)
            head, _, tail = rest.partition("(")
            toks = head.strip().split()
            opcode = toks[-1] if toks else ""  # last token before '('
            out_b = shapes.get(m.group(1), 0.0)
            if mode == "fused":
                is_param = opcode.startswith("parameter")
                is_root = line.lstrip().startswith("ROOT")
                is_major = any(opcode.startswith(s.strip("( "))
                               for s in _MAJOR_OPS)
                if is_param or is_root or is_major:
                    total += 2.0 * out_b
                continue
            if any(opcode.startswith(s) for s in _SKIP_OPS):
                continue
            depth, j = 1, 0
            for j, ch in enumerate(tail):
                if ch == "(":
                    depth += 1
                elif ch == ")":
                    depth -= 1
                    if depth == 0:
                        break
            opnames = _NAME_RE.findall(tail[:j])
            in_b = sum(shapes.get(n, 0.0) for n in opnames)
            total += out_b + in_b
    return total


# ---------------------------------------------------------------------------
# Sharded temporal-blocking traffic model (FHP extended-shard hot path).
#
# Each shard owns ``hl`` rows x ``wdl`` packed words of the global lattice
# and exchanges a depth-``d`` halo (2d rows + 2 words per round) to run d
# local steps per ppermute round, executed as ceil(d/T) fused Pallas
# launches of T in-kernel steps on the (hl + 2d)-row extended array.  The
# model prices the three costs the (block_rows, T, depth) autotuner trades:
#
#   HBM      -- the extended stack crosses HBM once per launch plus the
#               2T/bh halo-band re-reads of the overlapping BlockSpecs;
#   ICI      -- halo bytes per exchange, amortised over d steps;
#   latency  -- a fixed per-exchange term (ppermute round trip + launch
#               overheads), amortised over d steps -- the paper's
#               "two barriers per step" cost, and the reason exchange
#               *count* matters independently of exchange *bytes*.
#
# Redundant apron compute is priced in HBM-row-equivalents via
# ``compute_row_weight`` (the kernel is memory-bound, so apron rows are
# cheap but not free).  All numbers are per *useful* site update.
# ---------------------------------------------------------------------------

PLANE_BYTES = 8 * 4            # 8 uint32 bit-planes per word of 32 nodes
DYN_PLANE_BYTES = 7 * 4        # the 7 dynamic planes (static-solid mode)
WORD_NODES = 32
EXCHANGE_LATENCY_S = 3e-6      # fallback cost per halo-exchange round

# Measured ppermute round-trip latency, filled lazily by
# ``measured_exchange_latency`` and keyed by the attached mesh's
# fingerprint: repeated ``autotune_launch`` calls (the joint search calls
# the model thousands of times) must not re-run the microbench, but a
# process that re-attaches to a different topology (fake-device
# subprocess, multi-host restart) must not inherit a stale number either.
_MEASURED_EXCHANGE_LATENCY: Dict[tuple, float] = {}


def _mesh_fingerprint() -> tuple:
    """Static identity of the attached device topology: backend, device
    count, and device kind.  Cheap (no collectives) and stable for the
    process lifetime unless the platform itself is re-selected."""
    import jax
    devs = jax.devices()
    return (jax.default_backend(), len(devs), devs[0].device_kind)


def measured_exchange_latency(refresh: bool = False) -> float:
    """Per-exchange latency for the traffic model, measured when possible.

    On a real multi-chip mesh (>= 2 non-CPU devices) this times a ring
    ``ppermute`` of one tiny buffer over a 1-D mesh -- jitted, warmed,
    best of 3 trials of 64 rounds -- and caches the per-round seconds
    under the mesh fingerprint (backend, device count, device kind), so
    repeated ``autotune_launch`` calls never re-run the microbench while
    a topology change invalidates the cache naturally.  A failure of the
    microbench raises: a constant in its place would hide a broken mesh.
    On CPU / single-device backends ``ppermute`` is a host memcpy whose
    timing says nothing about ICI, so the ``EXCHANGE_LATENCY_S`` constant
    is returned unchanged (keeps the model, the autotuner, and every test
    deterministic off-mesh)."""
    key = _mesh_fingerprint()
    if key in _MEASURED_EXCHANGE_LATENCY and not refresh:
        return _MEASURED_EXCHANGE_LATENCY[key]
    backend, n, _kind = key
    lat = EXCHANGE_LATENCY_S
    if backend != "cpu" and n >= 2:
        import jax
        import jax.numpy as jnp
        from jax import lax
        from jax.sharding import NamedSharding, PartitionSpec as P

        from repro.core.distributed import _ring, _shard_map, make_mesh

        rounds = 64
        mesh = make_mesh((n,), ("x",))

        def chain(x):
            def body(_, v):
                return lax.ppermute(v, "x", _ring(n, up=True))
            return lax.fori_loop(0, rounds, body, x)

        g = jax.jit(_shard_map(chain, mesh, (P("x"),), P("x")))
        x = jax.device_put(jnp.zeros((8 * n, 128), jnp.float32),
                           NamedSharding(mesh, P("x")))
        g(x).block_until_ready()           # compile + warm
        best = min(_timed(g, x) for _ in range(3))
        lat = max(best / rounds, 1e-8)
    _MEASURED_EXCHANGE_LATENCY[key] = lat
    return lat


def _timed(g, x) -> float:
    import time
    t0 = time.perf_counter()
    g(x).block_until_ready()
    return time.perf_counter() - t0


def _ceil_to(x: int, m: int) -> int:
    return -(-x // m) * m


def _pow2_ge(x: int) -> int:
    p = 1
    while p < x:
        p *= 2
    return p


def sharded_fhp_traffic(hl: int, wdl: int, *, depth: int, T: int,
                        block_rows: int, block_words: int = 0,
                        compute_row_weight: float = 0.2,
                        exchange_latency_s: float = EXCHANGE_LATENCY_S,
                        hw: HW = V5E,
                        static_solid: bool = False,
                        n_planes: int = 8,
                        overlap: bool = False) -> Dict[str, float]:
    """Modeled per-site-step costs of the sharded Pallas hot path.

    Returns a dict with ``hbm_bytes_per_site_step`` (the headline number:
    acceptance target <= 0.6 at depth >= 4), ``ici_bytes_per_site_step``,
    ``exchanges_per_step``, ``launches_per_step``, and the roofline-style
    time decomposition ``{hbm,compute,ici,latency,total}_s_per_site``.

    ``block_words`` (0 / >= width = the legacy full-width 1-D band)
    prices the 2-D (x x y) blocked kernel grid: each tile re-reads a
    T-word x apron per side per launch and the redundant-compute extents
    shrink in both axes -- the x-apron redundancy term the joint
    ``(block_rows, block_words, T, depth)`` autotuner trades against the
    VMEM ceiling.  The extended width ``wdl + 2`` is word-padded to a
    block multiple, exactly like the row padding.

    ``static_solid`` prices the static-geometry cache: the solid plane is
    exchanged once per geometry (its one-time cost is reported as
    ``geometry_exchange_bytes``, excluded from the per-step totals) and
    every round moves the 7 *dynamic* planes over ICI -- a 7/8 cut of the
    plane term -- while each launch writes 7 planes back to HBM instead
    of 8 (reads stay at 8: the kernel still consumes the solid band).

    ``n_planes`` is the rule's plane count (``core.rulespec``): bytes
    per word-cell scale linearly with it, so e.g. 2-plane BML moves a
    quarter of FHP's HBM and exchange bytes per site-step.  The default
    8 reproduces the historic FHP numbers exactly.

    ``overlap`` prices the compute/communication-overlapped schedule
    (``ops.run_extended_split``): each round issues the halo ``ppermute``
    ring concurrently with an *interior* launch set on the bare
    ``(hl, wdl)`` shard (whose depth-d light cone never touches the
    apron), then a thin *boundary* launch set -- two ``3d``-row bands and
    two 3-word column strips -- once halos land, so

        ``total = max(t_exchange, t_interior) + t_boundary``

    instead of the serial sum.  The split is priced honestly: interior +
    boundary launches together read slightly more HBM than one full
    extended launch (each boundary slice pays its own T-row/T-word
    apron), so the overlap win is ``min(t_exchange, t_interior)`` minus
    that split overhead, and exactly the quantity
    ``overlap_speedup_modeled`` reports against the serial model.  The
    reported plan is the *better* of split and serial: when the boundary
    band covers the whole shard (``hl <= 2*depth`` or ``wdl <= 2``, the
    stepper's runtime fallback) or when the split overhead exceeds the
    hidden exchange time (tiny shards, where the tuner keeps the serial
    plan), the model reports the serial schedule --
    ``t_interior_s_per_site`` is 0 and the modeled speedup exactly 1.
    Hence overlap models *strictly* lower cost than serial whenever the
    reported ``t_interior_s_per_site`` is positive.
    """
    assert 1 <= T <= block_rows and 1 <= depth, (T, block_rows, depth)
    plane_bytes = 4 * n_planes
    dyn_plane_bytes = 4 * (n_planes - 1)
    we = wdl + 2                               # extended width in words
    bw = min(block_words, we) if block_words else we
    x_blocked = bw < we
    assert not x_blocked or T <= bw, (T, bw)
    he = hl + 2 * depth
    # Launch schedule: full T-step launches plus one rem-step tail launch.
    ts = [T] * (depth // T) + ([depth % T] if depth % T else [])
    sites = float(hl * wdl * WORD_NODES)       # useful sites per shard step
    write_pb = dyn_plane_bytes if static_solid else plane_bytes
    xchg_pb = dyn_plane_bytes if static_solid else plane_bytes

    def component(he_c, we_c, bh_c, bw_c):
        """(HBM bytes, weighted-compute bytes) per round of one launch
        set covering a (he_c, we_c) sub-array with (bh_c, bw_c) tiles:
        per launch every tile reads (bh + 2*Tj) x (bw + 2*Tj_x) cells
        (all planes -- the solid band rides in either layout) and the
        padded array is written back once (7 or 8 planes); step s of a
        Tj-launch updates the shrinking apron extents of (cheap,
        weighted) redundant compute."""
        bw_c = min(bw_c, we_c)
        xb = bw_c < we_c
        he_cp = _ceil_to(he_c, bh_c)
        we_cp = _ceil_to(we_c, bw_c)
        nb_c, nbx_c = he_cp // bh_c, we_cp // bw_c
        hbm = sum(plane_bytes * nb_c * nbx_c * (bh_c + 2 * tj)
                  * (bw_c + (2 * tj if xb else 0))
                  + write_pb * he_cp * we_cp
                  for tj in ts)
        comp = compute_row_weight * plane_bytes * sum(
            nb_c * nbx_c * (bh_c + 2 * (tj - s - 1))
            * (bw_c + (2 * (tj - s - 1) if xb else 0))
            for tj in ts for s in range(tj))
        return hbm, comp

    # Serial launch set: the full extended array (legacy accounting).
    hbm_raw, comp_raw = component(he, we, block_rows, bw)
    hbm_b = hbm_raw / (sites * depth)
    comp_b = comp_raw / (sites * depth)
    we_p = _ceil_to(we, bw)
    nbx = we_p // bw

    # ICI: per exchange each shard sends depth rows up + depth rows down of
    # the x-extended width, plus one word column each side for the x halo;
    # static geometry drops the solid plane from every round.
    halo_words = 2 * depth * (wdl + 2) + 2 * hl
    ici_exchange_b = xchg_pb * halo_words
    ici_b = ici_exchange_b / (sites * depth)

    lat_s = exchange_latency_s / (sites * depth)
    hbm_s = hbm_b / hw.hbm_bw
    comp_s = comp_b / hw.hbm_bw
    ici_s = ici_b / hw.ici_bw
    out = {
        "block_words": float(bw),
        "x_blocks": float(nbx),
        "hbm_bytes_per_site_step": hbm_b,
        "compute_row_equiv_bytes_per_site_step": comp_b,
        "ici_bytes_per_site_step": ici_b,
        "ici_bytes_per_exchange": float(ici_exchange_b),
        # one-time solid-apron exchange (amortises to ~0 over a run)
        "geometry_exchange_bytes": float(4 * halo_words) if static_solid
                                   else 0.0,
        "static_solid": float(static_solid),
        "exchanges_per_step": 1.0 / depth,
        "launches_per_step": len(ts) / depth,
        "hbm_s_per_site": hbm_s,
        "compute_s_per_site": comp_s,
        "ici_s_per_site": ici_s,
        "latency_s_per_site": lat_s,
        "total_s_per_site": hbm_s + comp_s + ici_s + lat_s,
    }
    if not overlap:
        return out

    serial_s = out["total_s_per_site"]
    exchange_s = ici_s + lat_s
    interior_ok = hl > 2 * depth and wdl > 2

    def as_serial():
        # The overlap plan degenerates to the serial schedule: either the
        # boundary band covers the whole shard (the stepper's runtime
        # fallback) or the split's apron overhead exceeds the hidden
        # exchange time, in which case the tuner keeps the serial plan
        # (ties break serial).  Either way the reported plan *is* serial:
        # no interior time, modeled speedup exactly 1.
        out.update({
            "overlap": 0.0,
            "t_exchange_s_per_site": exchange_s,
            "t_interior_s_per_site": 0.0,
            "t_boundary_s_per_site": hbm_s + comp_s,
            "serial_s_per_site": serial_s,
            "overlap_speedup_modeled": 1.0,
        })
        return out

    if not interior_ok:
        return as_serial()

    # Interior: the bare (hl, wdl) shard (no apron dependence); boundary:
    # two 3d-row bands at full extended width plus two 3-word column
    # strips over the interior rows -- the exact launch restriction of
    # ``ops.run_extended_split``, each slice's tile capped to its extent.
    bh_i = min(block_rows, _pow2_ge(hl))
    hbm_i, comp_i = component(hl, wdl, bh_i, bw)
    bh_tb = min(block_rows, _pow2_ge(3 * depth))
    hbm_tb, comp_tb = component(3 * depth, we, bh_tb, bw)
    hbm_lr, comp_lr = component(hl, 3, bh_i, 3)      # strips: full width
    hbm_bnd = 2 * (hbm_tb + hbm_lr)
    comp_bnd = 2 * (comp_tb + comp_lr)

    interior_s = (hbm_i + comp_i) / (sites * depth) / hw.hbm_bw
    boundary_s = (hbm_bnd + comp_bnd) / (sites * depth) / hw.hbm_bw
    total_s = max(exchange_s, interior_s) + boundary_s
    if total_s >= serial_s:
        return as_serial()
    out.update({
        "overlap": 1.0,
        "hbm_bytes_per_site_step": (hbm_i + hbm_bnd) / (sites * depth),
        "compute_row_equiv_bytes_per_site_step":
            (comp_i + comp_bnd) / (sites * depth),
        "hbm_s_per_site": (hbm_i + hbm_bnd) / (sites * depth) / hw.hbm_bw,
        "compute_s_per_site":
            (comp_i + comp_bnd) / (sites * depth) / hw.hbm_bw,
        "launches_per_step": 5 * len(ts) / depth,
        "t_exchange_s_per_site": exchange_s,
        "t_interior_s_per_site": interior_s,
        "t_boundary_s_per_site": boundary_s,
        "serial_s_per_site": serial_s,
        "total_s_per_site": total_s,
        "overlap_speedup_modeled": serial_s / total_s,
    })
    return out


def roofline_terms(flops: float, bytes_: float, coll_bytes: float,
                   hw: HW = V5E) -> Dict[str, float]:
    t_c = flops / hw.peak_flops
    t_m = bytes_ / hw.hbm_bw
    t_x = coll_bytes / hw.ici_bw
    dom = max(("compute", t_c), ("memory", t_m), ("collective", t_x),
              key=lambda kv: kv[1])[0]
    return {"compute_s": t_c, "memory_s": t_m, "collective_s": t_x,
            "bound": dom, "step_s_lower_bound": max(t_c, t_m, t_x)}


def analyze_compiled(compiled, *, model_flops: Optional[float] = None,
                     chips: int = 1, hw: HW = V5E) -> Dict:
    """Full per-device roofline record for one compiled executable.

    ``model_flops`` is the *global* useful-model FLOPs per step (6*N*D
    etc.); the record reports MODEL_FLOPS / (HLO_FLOPs * chips).
    """
    ca = compiled.cost_analysis() or {}
    if isinstance(ca, (list, tuple)):
        ca = ca[0] if ca else {}
    flops = float(ca.get("flops", 0.0))
    text = compiled.as_text()
    bytes_ = hbm_bytes_estimate(text, mode="fused")
    bytes_xla = float(ca.get("bytes accessed", 0.0))
    colls = collective_bytes(text)
    coll_b = colls["_total"]["operand_bytes"]
    terms = roofline_terms(flops, bytes_, coll_b, hw)

    mem = {}
    try:
        ma = compiled.memory_analysis()
        for k in ("argument_size_in_bytes", "output_size_in_bytes",
                  "temp_size_in_bytes", "generated_code_size_in_bytes",
                  "alias_size_in_bytes"):
            if hasattr(ma, k):
                mem[k] = int(getattr(ma, k))
    except Exception:
        pass

    rec = {
        "flops_per_device": flops,
        "bytes_per_device": bytes_,
        "bytes_xla_prefusion_per_device": bytes_xla,
        "collective_bytes_per_device": coll_b,
        "collective_wire_bytes_per_device": colls["_total"]["wire_bytes"],
        "collectives": {k: v for k, v in colls.items() if k != "_total"
                        and v["count"]},
        "terms": terms,
        "memory_analysis": mem,
    }
    if model_flops is not None:
        hlo_global = flops * chips
        rec["model_flops_global"] = model_flops
        rec["model_flops_ratio"] = (model_flops / hlo_global
                                    if hlo_global else 0.0)
        rec["roofline_fraction"] = (
            (model_flops / chips / hw.peak_flops)
            / terms["step_s_lower_bound"]
            if terms["step_s_lower_bound"] > 0 else 0.0)
    return rec
