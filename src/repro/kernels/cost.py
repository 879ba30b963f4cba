"""Analytical kernel cost model — the roofline half of the autotuner.

The paper allocates LOAD to heterogeneous workers from a per-worker cost
model (§IV, Algorithm 1); this module is the same idea one level down:
allocate each (op, shape) to the cheapest KERNEL IMPLEMENTATION from a
per-candidate cost model, so `kernel_mode="auto"` (repro.kernels.dispatch)
can pick winners for shapes nobody benchmarked.

Candidates per op (the grid `tools/autotune.py` measures):

  * ``coded_linear``        — ``default`` (XLA block matmul + mask-keyed
    cached decode), ``svd`` (the seed's in-graph pinv + 2 refinement
    steps), ``fused`` (matmul+decode in one dataflow: the Pallas kernel on
    TPU, the jnp oracle under XLA fusion on CPU);
  * ``coded_matvec`` / ``coded_matvec_decode`` / ``gaussian_encode`` /
    ``lt_encode`` — ``ref`` (jnp oracle) vs ``pallas`` (tiled kernel, with
    tile parameters from :func:`choose_*_tiles`).

Each candidate is summarized as a :class:`KernelCost` — dot FLOPs, HBM
bytes, a materializing-op count (dispatch-graph overhead proxy), and a
small-SVD work term — priced against a :class:`HostHardware`:

    t_us = dispatch + node_us·nodes + svd_us·svd_n3
           + combine(flops/gemm_flops, bytes/mem_bw)

``combine`` is ``max`` on hardware that overlaps DMA with compute (TPU —
the classical roofline) and ``+`` on the CPU host container, where XLA's
single-threaded-ish eager dispatch does not hide memory behind compute.
The constants are CALIBRATED: :func:`fit_hardware` least-squares fits them
to the measured candidate grid (non-negative, active-set clamping), and the
fitted values are persisted in ``reports/bench/autotune.json`` so the
analytical fallback for unseen shapes extrapolates from real measurements
rather than spec sheets.  ``model_error`` (max(pred, meas)/min(pred, meas))
above :data:`MODEL_ERROR_FLAG` marks a cell where the model needs work;
:data:`MODEL_ERROR_BOUND` is the hard gate ``tools/bench_compare.py`` and
tests/test_autotune.py enforce on committed winners.

Interpret-mode Pallas timings are interpreter overhead, not kernel cost —
they are never candidates here (DESIGN.md §11).

Tile choosers mirror the VMEM-budget notes in the kernel docstrings
(coded_matvec.py, coded_decode.py, lt_encode.py): search MXU-aligned tile
grids for minimum modeled HBM traffic + grid overhead under the
double-buffered VMEM budget.
"""
from __future__ import annotations

from dataclasses import dataclass, replace

from repro.core.coded_ops import block_rows

__all__ = [
    "HostHardware",
    "CPU_HOST",
    "TPU_V5E_HOST",
    "KernelCost",
    "MODEL_ERROR_FLAG",
    "MODEL_ERROR_BOUND",
    "coded_linear_costs",
    "matvec_costs",
    "matvec_decode_costs",
    "encode_costs",
    "candidate_costs",
    "choose_matvec_tiles",
    "choose_decode_tiles",
    "choose_encode_tiles",
    "fit_hardware",
    "predict_best",
    "model_error",
    "recommended_max_patterns",
    "decoder_cache_worthwhile",
]

MODEL_ERROR_FLAG = 2.0    # reconcile pass flags cells the model misses by >2x
MODEL_ERROR_BOUND = 4.0   # hard gate on committed winners (bench_compare, tests)

_F32 = 4  # bytes

# Pallas VMEM working-set budget: 16 MB VMEM, double-buffered pipelines need
# 2x the tile set resident (kernel docstrings size their defaults to ~half)
VMEM_BYTES = 16 * 2**20
VMEM_TILE_BUDGET = VMEM_BYTES // 2


@dataclass(frozen=True)
class HostHardware:
    """Calibratable execution-cost constants for one backend."""

    name: str
    gemm_flops: float    # sustained f32 dot throughput, flop/s
    mem_bw: float        # sustained memory bandwidth, bytes/s
    dispatch_us: float   # fixed per-call overhead (jit dispatch floor)
    node_us: float       # per materializing-op overhead (graph size proxy)
    svd_us: float        # per unit of svd_n3 (in-graph small-SVD work)
    overlap: bool        # True: max(compute, memory) roofline; False: sum
    step_us: float = 0.5  # per Pallas-grid-step overhead (tile choosers)

    def as_dict(self) -> dict:
        return {
            "name": self.name, "gemm_flops": self.gemm_flops,
            "mem_bw": self.mem_bw, "dispatch_us": self.dispatch_us,
            "node_us": self.node_us, "svd_us": self.svd_us,
            "overlap": self.overlap, "step_us": self.step_us,
        }

    @classmethod
    def from_dict(cls, d: dict) -> "HostHardware":
        return cls(**{k: d[k] for k in (
            "name", "gemm_flops", "mem_bw", "dispatch_us", "node_us",
            "svd_us", "overlap", "step_us",
        )})


# Pre-calibration priors.  CPU numbers are the observed behaviour of the
# jitted XLA-CPU paths in this repo's benchmarks (the ~150 us dispatch
# floor is documented in benchmarks/decode_bench.py); autotune refits them.
CPU_HOST = HostHardware(
    name="cpu-host", gemm_flops=5e10, mem_bw=1.0e10,
    dispatch_us=50.0, node_us=5.0, svd_us=0.05, overlap=False,
)

# TPU v5e from utils/hlo.HW_V5E: 197 Tflop/s is bf16 peak; the coded paths
# accumulate in f32 (half rate on the MXU).  svd_us is set prohibitively
# high: an in-graph SVD custom-call on TPU breaks the step program
# (test_hlo.py asserts its absence) — the model must never pick it there.
TPU_V5E_HOST = HostHardware(
    name="tpu-v5e", gemm_flops=98.5e12, mem_bw=819e9,
    dispatch_us=3.0, node_us=0.5, svd_us=1e3, overlap=True, step_us=0.05,
)

_PRESETS = {"cpu": CPU_HOST, "tpu": TPU_V5E_HOST}
# the device kinds (jax ``Device.device_kind``) each accelerator preset
# describes: another chip's peaks are no prior for a chip
_PRESET_KINDS = {"tpu": ("TPU v5 lite",)}


def preset(backend: str, device_kind: str | None = None) -> HostHardware:
    """Hardware prior for a jax backend name; ``device_kind``, where given,
    must be one the preset describes.  A backend or device kind with no
    preset is an error, never another chip's numbers."""
    if backend not in _PRESETS:
        raise ValueError(f"no hardware preset for backend {backend!r}")
    kinds = _PRESET_KINDS.get(backend)
    if device_kind is not None and kinds is not None and device_kind not in kinds:
        raise ValueError(
            f"no hardware preset for {backend} device kind {device_kind!r} "
            f"(known: {', '.join(kinds)})"
        )
    return _PRESETS[backend]


@dataclass(frozen=True)
class KernelCost:
    """Cost features of one candidate implementation at one shape."""

    flops: float          # dot FLOPs (MXU/FMA work)
    bytes: float          # HBM traffic of materializing ops
    nodes: int            # materializing instructions (dispatch-graph proxy)
    svd_n3: float = 0.0   # small-SVD work scale (nb * n_data^2), svd impl only
    grid_steps: int = 0   # Pallas grid size (tile-chooser overhead term)

    def compute_us(self, hw: HostHardware) -> float:
        return self.flops / hw.gemm_flops * 1e6

    def memory_us(self, hw: HostHardware) -> float:
        return self.bytes / hw.mem_bw * 1e6

    def predicted_us(self, hw: HostHardware) -> float:
        c, m = self.compute_us(hw), self.memory_us(hw)
        roof = max(c, m) if hw.overlap else c + m
        return (hw.dispatch_us + hw.node_us * self.nodes
                + hw.svd_us * self.svd_n3 + hw.step_us * self.grid_steps
                + roof)

    def predicted_block_us(self, hw: HostHardware, k: int) -> float:
        """Cost of ``k`` fused iterations launched as ONE call (the macro-
        step decode trace, DESIGN.md §14): the jit dispatch floor is paid
        once, the body — graph nodes, svd work, grid steps, roofline — k
        times.  ``k=1`` is exactly :meth:`predicted_us`."""
        per_iter = self.predicted_us(hw) - hw.dispatch_us
        return hw.dispatch_us + max(1, int(k)) * per_iter


def model_error(predicted_us: float, measured_us: float) -> float:
    """Symmetric ratio error: max/min of (predicted, measured), >= 1."""
    lo, hi = sorted([max(predicted_us, 1e-9), max(measured_us, 1e-9)])
    return hi / lo


# --------------------------------------------------------------------------
# per-op candidate cost constructors
# --------------------------------------------------------------------------
def coded_linear_costs(
    out: int, inner: int, batch: int, n_data: int, n_parity: int,
    backend: str = "cpu",
) -> dict[str, KernelCost]:
    """Candidates for ``CodedLinear.apply`` at (out x inner x batch).

    ``fused`` means the single-dataflow matmul+decode: the Pallas kernel on
    TPU (coded partials never leave VMEM), the jnp oracle under XLA fusion
    on CPU (partials round-trip once, but no mask-multiply / lut machinery).
    """
    nb = n_data + n_parity
    br = block_rows(out, n_data)  # the stored float32 shape
    rows = nb * br
    gemm = 2.0 * rows * inner * batch
    dec = 2.0 * n_data * nb * br * batch
    w_b = _F32 * rows * inner
    x_b = _F32 * inner * batch
    yc_b = _F32 * rows * batch
    out_b = _F32 * n_data * br * batch
    costs = {
        # matmul -> reshape -> mask-multiply -> lut index ops -> rec gather
        # -> decode matmul -> slice: yc written, mask-mult read+write,
        # decode read — 4 passes over the coded partials
        "default": KernelCost(
            flops=gemm + dec, bytes=w_b + x_b + 4 * yc_b + out_b, nodes=14,
        ),
        # seed fallback: pinv (small SVD) + initial solve + 2 refinement
        # steps = 5 extra rec-sized matmuls' worth of passes over partials
        "svd": KernelCost(
            flops=gemm + 5.0 * dec, bytes=w_b + x_b + 6 * yc_b + out_b,
            nodes=20, svd_n3=float(nb * n_data * n_data),
        ),
    }
    if backend == "cpu":
        # jnp oracle: two dots, partials round-trip exactly once
        costs["fused"] = KernelCost(
            flops=gemm + dec, bytes=w_b + x_b + 2 * yc_b + out_b, nodes=6,
        )
    else:
        tiles = choose_decode_tiles(br, inner, batch, nb, n_data)
        costs["fused"] = KernelCost(
            flops=gemm + dec, bytes=w_b + x_b + out_b, nodes=4,
            grid_steps=tiles.pop("grid_steps"),
        )
    return costs


def matvec_costs(r: int, m: int, b: int, backend: str = "cpu") -> dict[str, KernelCost]:
    """Candidates for the tiled coded matvec y = A x ([r, m] x [m, b])."""
    gemm = 2.0 * r * m * b
    io = _F32 * (r * m + m * b + r * b)
    costs = {"ref": KernelCost(flops=gemm, bytes=io, nodes=3)}
    if backend != "cpu":
        tiles = choose_matvec_tiles(r, m, b)
        costs["pallas"] = KernelCost(
            flops=gemm, bytes=io, nodes=2, grid_steps=tiles.pop("grid_steps"),
        )
    return costs


def matvec_decode_costs(
    rows: int, m: int, b: int, n_data: int, n_blocks: int,
    backend: str = "cpu",
) -> dict[str, KernelCost]:
    """Candidates for the raw fused matmul+decode (rec already resolved)."""
    br = rows // n_blocks
    gemm = 2.0 * rows * m * b
    dec = 2.0 * n_data * n_blocks * br * b
    w_b, x_b = _F32 * rows * m, _F32 * m * b
    yc_b, out_b = _F32 * rows * b, _F32 * n_data * br * b
    costs = {
        "ref": KernelCost(flops=gemm + dec, bytes=w_b + x_b + 2 * yc_b + out_b,
                          nodes=5),
    }
    if backend != "cpu":
        tiles = choose_decode_tiles(br, m, b, n_blocks, n_data)
        costs["pallas"] = KernelCost(
            flops=gemm + dec, bytes=w_b + x_b + out_b, nodes=3,
            grid_steps=tiles.pop("grid_steps"),
        )
    return costs


def encode_costs(
    kind: str, q: int, r: int, m: int, d_max: int = 0, backend: str = "cpu",
) -> dict[str, KernelCost]:
    """Candidates for the encode kernels (dense gaussian / sparse LT)."""
    if kind == "gaussian":
        gemm = 2.0 * q * r * m
        io = _F32 * (q * r + r * m + q * m)
        costs = {"ref": KernelCost(flops=gemm, bytes=io, nodes=3)}
        if backend != "cpu":
            tiles = choose_encode_tiles(q, r, m)
            costs["pallas"] = KernelCost(
                flops=gemm, bytes=io, nodes=2,
                grid_steps=tiles.pop("grid_steps"),
            )
        return costs
    if kind == "lt":
        # gather + weighted accumulate: bandwidth-bound (lt_encode.py)
        fma = 2.0 * q * d_max * m
        io = _F32 * (q * d_max * m + q * m + 2 * q * d_max)
        costs = {"ref": KernelCost(flops=fma, bytes=io, nodes=4)}
        if backend != "cpu":
            bm = min(512, _pow2_floor(m))
            steps = q * max(1, -(-m // bm)) * max(1, d_max)
            costs["pallas"] = KernelCost(
                flops=fma, bytes=io, nodes=2, grid_steps=steps,
            )
        return costs
    raise ValueError(f"unknown encode kind {kind!r}")


def candidate_costs(op: str, backend: str, **geom) -> dict[str, KernelCost]:
    """Dispatch to the per-op constructor by table op name."""
    if op == "coded_linear":
        return coded_linear_costs(
            geom["out"], geom["inner"], geom["batch"],
            geom["n_data"], geom["n_parity"], backend,
        )
    if op == "coded_matvec":
        return matvec_costs(geom["r"], geom["m"], geom["b"], backend)
    if op == "coded_matvec_decode":
        return matvec_decode_costs(
            geom["rows"], geom["m"], geom["b"],
            geom["n_data"], geom["n_blocks"], backend,
        )
    if op in ("gaussian_encode", "lt_encode"):
        return encode_costs(
            op.split("_")[0], geom["q"], geom["r"], geom["m"],
            geom.get("d_max", 0), backend,
        )
    raise ValueError(f"unknown op {op!r}")


# --------------------------------------------------------------------------
# tile choosers (TPU compile mode) — VMEM-budget search, traffic objective
# --------------------------------------------------------------------------
def _pow2_floor(n: int) -> int:
    p = 1
    while p * 2 <= n:
        p *= 2
    return p


def _tile_search(candidates, vmem_of, traffic_of, steps_of,
                 hw: HostHardware = TPU_V5E_HOST):
    best, best_t = None, float("inf")
    for c in candidates:
        if vmem_of(*c) > VMEM_TILE_BUDGET:  # double-buffering doubles this
            continue
        t = traffic_of(*c) / hw.mem_bw * 1e6 + steps_of(*c) * hw.step_us
        # tie-break toward larger tiles (fewer steps, better MXU occupancy)
        if t < best_t - 1e-9:
            best, best_t = c, t
    if best is None:  # degenerate small shapes: smallest candidate
        best = min(candidates, key=lambda c: vmem_of(*c))
    return best


def choose_matvec_tiles(r: int, m: int, b: int) -> dict:
    """(block_r, block_m) for coded_matvec_pallas — A tile + x panel + out
    block double-buffered under VMEM; x is re-read once per row block, so
    taller row blocks trade A-tile VMEM against x re-reads."""
    cands = [(br_, bm_) for br_ in (128, 256, 512, 1024)
             for bm_ in (256, 512, 1024, 2048)]

    def vmem(br_, bm_):
        return _F32 * (br_ * bm_ + bm_ * b + br_ * b)

    def traffic(br_, bm_):
        return _F32 * (r * m + -(-r // br_) * m * b + r * b)

    def steps(br_, bm_):
        return -(-r // br_) * -(-m // bm_)

    br_, bm_ = _tile_search(cands, vmem, traffic, steps)
    return {"block_r": br_, "block_m": bm_, "grid_steps": steps(br_, bm_)}


def choose_decode_tiles(br: int, m: int, b: int, n_blocks: int,
                        n_data: int) -> dict:
    """(block_t, block_m) for coded_matvec_decode_pallas — the W tile spans
    all n_blocks (coded_decode.py), so VMEM scales with nb·BT·BM."""
    cands = [(bt_, bm_) for bt_ in (64, 128, 256)
             for bm_ in (256, 512, 1024)]

    def vmem(bt_, bm_):
        return _F32 * (n_blocks * bt_ * bm_ + bm_ * b + n_data * bt_ * b
                       + n_data * n_blocks)

    def traffic(bt_, bm_):
        return _F32 * (n_blocks * br * m + -(-br // bt_) * m * b
                       + n_data * br * b)

    def steps(bt_, bm_):
        return -(-br // bt_) * -(-m // bm_)

    bt_, bm_ = _tile_search(cands, vmem, traffic, steps)
    return {"block_t": bt_, "block_m": bm_, "grid_steps": steps(bt_, bm_)}


def choose_encode_tiles(q: int, r: int, m: int) -> dict:
    """(block_q, block_m, block_r) for gaussian_encode_pallas — G tile is
    re-read per column panel, A tile per row panel (lt_encode.py)."""
    cands = [(bq_, bm_, bk_) for bq_ in (64, 128, 256)
             for bm_ in (256, 512, 1024) for bk_ in (256, 512)]

    def vmem(bq_, bm_, bk_):
        return _F32 * (bq_ * bk_ + bk_ * bm_ + bq_ * bm_)

    def traffic(bq_, bm_, bk_):
        return _F32 * (-(-m // bm_) * q * r + -(-q // bq_) * r * m + q * m)

    def steps(bq_, bm_, bk_):
        return -(-q // bq_) * -(-m // bm_) * -(-r // bk_)

    bq_, bm_, bk_ = _tile_search(cands, vmem, traffic, steps)
    return {"block_q": bq_, "block_m": bm_, "block_r": bk_,
            "grid_steps": steps(bq_, bm_, bk_)}


def tile_params(op: str, **geom) -> dict:
    """Pallas tile parameters (without the grid_steps bookkeeping key)."""
    if op == "coded_matvec":
        p = choose_matvec_tiles(geom["r"], geom["m"], geom["b"])
    elif op in ("coded_linear", "coded_matvec_decode"):
        if op == "coded_linear":
            nb = geom["n_data"] + geom["n_parity"]
            br = block_rows(geom["out"], geom["n_data"])
            p = choose_decode_tiles(br, geom["inner"], geom["batch"],
                                    nb, geom["n_data"])
        else:
            p = choose_decode_tiles(geom["rows"] // geom["n_blocks"],
                                    geom["m"], geom["b"],
                                    geom["n_blocks"], geom["n_data"])
    elif op == "gaussian_encode":
        p = choose_encode_tiles(geom["q"], geom["r"], geom["m"])
    elif op == "lt_encode":
        p = {"block_m": min(512, _pow2_floor(geom["m"])), "grid_steps": 0}
    else:
        raise ValueError(f"unknown op {op!r}")
    p.pop("grid_steps", None)
    return p


# --------------------------------------------------------------------------
# calibration: fit HostHardware constants to measured (cost, us) samples
# --------------------------------------------------------------------------
def fit_hardware(
    samples: list[tuple[KernelCost, float]],
    base: HostHardware = CPU_HOST,
) -> HostHardware:
    """Non-negative least-squares fit of (dispatch, node, 1/gemm, 1/bw,
    svd) to measured timings; coefficients clamped at zero are re-solved
    without their column (active-set style).  Terms the sample set cannot
    identify (e.g. no svd candidate measured) keep ``base``'s value.

    Only valid for non-overlapping hardware (the additive form is linear);
    overlap=True presets are returned untouched.
    """
    import numpy as np

    if base.overlap or len(samples) < 3:
        return base
    feats = np.array(
        [[1.0, c.nodes, c.flops, c.bytes, c.svd_n3] for c, _ in samples]
    )
    y = np.array([us for _, us in samples], dtype=np.float64)
    active = [i for i in range(feats.shape[1]) if feats[:, i].any()]
    coef = np.zeros(feats.shape[1])
    for _ in range(feats.shape[1]):
        if not active:
            break
        a = feats[:, active]
        scale = np.abs(a).max(axis=0)
        sol, *_ = np.linalg.lstsq(a / scale, y, rcond=None)
        sol = sol / scale
        neg = [active[i] for i, s in enumerate(sol) if s < 0]
        if not neg:
            coef[active] = sol
            break
        active = [i for i in active if i not in neg]
    d_us, n_us, f_inv, b_inv, s_us = coef
    return replace(
        base,
        name=base.name + "-fitted",
        dispatch_us=float(d_us) if d_us > 0 else base.dispatch_us,
        node_us=float(n_us) if n_us > 0 else 0.0,
        gemm_flops=float(1e6 / f_inv) if f_inv > 0 else base.gemm_flops,
        mem_bw=float(1e6 / b_inv) if b_inv > 0 else base.mem_bw,
        svd_us=float(s_us) if s_us > 0 else base.svd_us,
    )


def predict_best(
    op: str, backend: str, hw: HostHardware | None = None,
    macro_k: int = 1, **geom
) -> tuple[str, float, dict]:
    """Analytical winner for an unseen shape: (impl, predicted_us, params).

    Interpret mode is never a candidate (it is not kernel performance), so
    on CPU the Pallas impls are simply absent from the grid; on TPU the
    chosen impl carries its tile parameters.

    ``macro_k > 1`` ranks candidates by the fused-block cost
    (:meth:`KernelCost.predicted_block_us`) — the dispatch floor amortizes
    over the k iterations of a macro-step trace, which can flip a winner
    whose only edge was lower per-call overhead.  The returned time is the
    per-iteration share (block / k), so it stays comparable with measured
    per-call rows; at ``macro_k=1`` both ranking and value are unchanged.
    """
    hw = hw or preset(backend)
    k = max(1, int(macro_k))
    costs = candidate_costs(op, backend, **geom)
    impl = min(costs, key=lambda c: costs[c].predicted_block_us(hw, k))
    params = (
        tile_params(op, **geom)
        if backend != "cpu" and impl in ("fused", "pallas")
        else {}
    )
    return impl, costs[impl].predicted_block_us(hw, k) / k, params


# --------------------------------------------------------------------------
# DecoderCache economics: is precomputing every pattern worth it, and how
# many patterns should the lut bound allow?
# --------------------------------------------------------------------------
def decodable_patterns(n_data: int, n_parity: int) -> int:
    from math import comb

    nb = n_data + n_parity
    return sum(comb(nb, e) for e in range(n_parity + 1))


def recommended_max_patterns(
    hw: HostHardware = CPU_HOST,
    table_budget_bytes: int = 32 * 2**20,
    build_budget_us: float = 60e6,
    n_blocks: int = 20,
    n_data: int = 16,
) -> int:
    """Largest pattern count worth precomputing: the table must fit the
    budget ([patterns, n_data, n_blocks] f32) and the one-time pinv build
    (svd_us per pattern's nb·n_data² work) must amortize inside the build
    budget.  The decoding.MAX_LUT_PATTERNS=8192 constant sits under both
    bounds for every geometry the lut accepts — asserted in tests."""
    by_mem = table_budget_bytes // (_F32 * n_data * n_blocks)
    per_pattern_us = max(hw.svd_us, 1e-3) * n_blocks * n_data * n_data
    by_build = int(build_budget_us / per_pattern_us)
    return min(by_mem, by_build)


def decoder_cache_worthwhile(
    n_data: int, n_parity: int, hw: HostHardware = CPU_HOST
) -> bool:
    """True when the full pattern table for this geometry is within the
    recommended bound (mirrors ``decoding.cacheable`` economics)."""
    return decodable_patterns(n_data, n_parity) <= recommended_max_patterns(hw)
