"""Roofline algebra — paper §3 (staging-tier roofline) + cluster roofline.

Two levels:

1. **Kernel-level** (the paper's analysis): arithmetic intensity of a
   register/VREG-blocked MMA fed from the staging tier (GPU: shared memory,
   TPU: VMEM).  Paper Eq. (1): AI(n) = n/5 for fp16 in / fp32 acc square
   blocking; we generalize to arbitrary dtypes and the TCEC pass structure
   (Fig. 7), and compute the B/F crossover that shows when the staging tier
   bounds the matrix unit.

2. **Cluster-level** (EXPERIMENTS.md §Roofline): the three-term model
   compute/memory/collective evaluated from a compiled dry-run artifact.
"""
from __future__ import annotations

import dataclasses
import os
from typing import Dict, Optional, Tuple


@dataclasses.dataclass(frozen=True)
class ChipSpec:
    name: str
    matrix_tflops: float          # peak matrix-unit TFLOP/s (bf16/fp16)
    vector_tflops: float          # peak fp32 vector-unit TFLOP/s
    hbm_gbps: float               # HBM bandwidth GB/s
    staging_gbps: float           # staging tier bandwidth GB/s (SMEM agg / VMEM)
    staging_kib: float            # staging capacity per core (KiB)
    ici_gbps_per_link: float = 0.0
    hbm_gib: float = 16.0


# Hardware constants from the assignment (+ paper Table 1 for context).
TPU_V5E = ChipSpec(
    name="tpu-v5e", matrix_tflops=197.0, vector_tflops=197.0 / 4,
    hbm_gbps=819.0, staging_gbps=22_000.0, staging_kib=128 * 1024,
    ici_gbps_per_link=50.0, hbm_gib=16.0,
)
A100_SXM4 = ChipSpec(
    name="a100-sxm4", matrix_tflops=312.0, vector_tflops=19.5,
    hbm_gbps=1555.0, staging_gbps=19_491.0, staging_kib=164,
)
V100_SXM2 = ChipSpec(
    name="v100-sxm2", matrix_tflops=112.0, vector_tflops=15.7,
    hbm_gbps=900.0, staging_gbps=14_131.0, staging_kib=96,
)

CHIPS = {c.name: c for c in (TPU_V5E, A100_SXM4, V100_SXM2)}

#: ``device_kind`` as JAX reports it -> the spec of that TPU.
TPU_DEVICE_KINDS = {"TPU v5 lite": TPU_V5E}


def active_chip() -> ChipSpec:
    """The chip the analytic models target.

    On a TPU backend the chip is read from the device: its ``device_kind``
    must be in ``TPU_DEVICE_KINDS`` (an unknown TPU raises rather than
    borrowing another part's peaks), and ``REPRO_CHIP``, if set, must name
    the same spec.  Off the TPU, ``REPRO_CHIP`` selects any registered
    ``ChipSpec`` by name and the default is the v5e (the repo's reference
    part), which keeps every derived constant — block caps, roofline
    bounds, tuner scores — identical on the CPU test backend and on a v5e.
    """
    import jax
    name = os.environ.get("REPRO_CHIP")
    if name and name not in CHIPS:
        raise KeyError(f"REPRO_CHIP={name!r} is not a registered chip; "
                       f"known: {sorted(CHIPS)}")
    device = jax.devices()[0]
    if device.platform != "tpu":
        return CHIPS[name] if name else TPU_V5E
    kind = device.device_kind
    if kind not in TPU_DEVICE_KINDS:
        raise ValueError(f"TPU device_kind {kind!r} has no ChipSpec; "
                         f"known: {sorted(TPU_DEVICE_KINDS)}")
    chip = TPU_DEVICE_KINDS[kind]
    if name and name != chip.name:
        raise ValueError(f"REPRO_CHIP={name!r} contradicts the device "
                         f"({kind!r} is {chip.name!r})")
    return chip


# ---------------------------------------------------------------------------
# Kernel-block-level model: footprints, caps and predicted times for the
# Pallas TCEC matmul family.  This is the single source of truth consumed by
# ``kernels.tcec_matmul.default_blocks`` and the ``repro.tune`` plan search —
# the paper's square-blocking AI(n) generalized to arbitrary (bm, bn, bk)
# tiles and the fused / staged / double-buffered-staged variants.
# ---------------------------------------------------------------------------

#: Slice of the staging tier one matmul's working set may claim.  Mosaic
#: keeps semaphores, spill slots and the co-resident epilogue operands
#: (bias/residual streams, attention scratch) in the same tier, so the
#: matmul cannot own it all; 1/64 is calibrated so the v5e reproduces the
#: empirically-good (128, 128, 512) caps that were previously hardcoded.
STAGING_BUDGET_FRACTION = 1.0 / 64.0

#: Mosaic double-buffers every BlockSpec-pipelined input stream.
PIPELINE_FACTOR = 2

# MXU/VREG alignment: sublane multiple for rows, lane multiple for cols.
SUBLANE = 8
LANE = 128

MATMUL_VARIANTS = ("fused", "staged", "staged_db", "vpu")


def staging_budget_bytes(chip: ChipSpec = None) -> int:
    """Staging-tier bytes one kernel's per-step working set may use."""
    chip = chip or active_chip()
    return int(chip.staging_kib * 1024 * STAGING_BUDGET_FRACTION)


def matmul_tile_footprint(bm: int, bn: int, bk: int, n_words: int,
                          variant: str = "fused") -> int:
    """Staging-tier bytes of one grid step's working set (paper Fig. 6).

    ``fused`` (WMMAe / on-the-fly) and ``vpu`` stream the fp32 source blocks
    (double-buffered by Mosaic) and keep the split words in VREGs; ``staged``
    (WMMA-API baseline) streams ``n_words`` bf16 word buffers per input
    instead; ``staged_db`` holds the word buffers in an explicit two-slot
    scratch (its own double buffering — inputs live in HBM/ANY, so Mosaic
    adds no pipeline copies on top).  All variants keep a (bm, bn) fp32
    accumulator resident across the k loop.
    """
    if variant not in MATMUL_VARIANTS:
        raise ValueError(f"unknown variant {variant!r}; one of "
                         f"{MATMUL_VARIANTS}")
    in_elems = bm * bk + bk * bn
    if variant in ("fused", "vpu"):
        in_bytes = PIPELINE_FACTOR * 4 * in_elems
    elif variant == "staged":
        in_bytes = PIPELINE_FACTOR * (2 * n_words) * in_elems
    else:  # staged_db: two explicit slots of all word buffers
        in_bytes = 2 * (2 * n_words) * in_elems
    return in_bytes + 4 * bm * bn


def derive_block_caps(chip: ChipSpec = None,
                      n_words: int = 3) -> Tuple[int, int, int]:
    """(bm_cap, bn_cap, bk_cap) tile caps derived from the chip.

    bm/bn: the paper's B/F crossover — the smallest square blocking whose
    AI(n) = n/5 reaches the staging-vs-matrix ratio (beyond it the MXU, not
    the staging tier, is the bound), rounded up to the lane width.  bk: the
    largest power-of-two multiple of the lane width whose worst-case
    (``staged``, ``n_words`` words, Mosaic-pipelined) footprint at
    (bm_cap, bn_cap, bk) fits the staging budget.  On the v5e this yields
    (128, 128, 512) — the previously hardcoded defaults, now derived.
    """
    chip = chip or active_chip()
    # AI needed to leave the staging-bandwidth roof: flops/byte.
    ai_star = chip.matrix_tflops * 1000.0 / chip.staging_gbps
    n_star = max(1, int(-(-5 * ai_star // 1)))        # AI(n) = n/5 crossover
    cap_mn = max(LANE, -(-n_star // LANE) * LANE)
    budget = staging_budget_bytes(chip)
    bk_cap = LANE
    while True:
        nxt = bk_cap * 2
        if matmul_tile_footprint(cap_mn, cap_mn, nxt, n_words,
                                 "staged") > budget:
            break
        bk_cap = nxt
    return cap_mn, cap_mn, bk_cap


def _pad_up(x: int, mult: int) -> int:
    return -(-x // mult) * mult


#: Fixed per-grid-step and per-launch overheads (seconds).  Small enough
#: never to dominate a realistic tile, large enough to break ties away from
#: degenerate many-step plans.  Purely analytic constants — deterministic
#: across processes by construction.
GRID_STEP_OVERHEAD_S = 2e-8
LAUNCH_OVERHEAD_S = 2e-6


def predict_matmul_time(m: int, n: int, k: int, *, batch: int = 1,
                        block: Tuple[int, int, int], variant: str = "fused",
                        passes: int = 6, n_words: int = 3,
                        rhs_batched: bool = True,
                        chip: ChipSpec = None) -> float:
    """Roofline-predicted seconds for the batched TCEC matmul.

    Three terms over the *padded* problem (padding waste is how oversized
    tiles lose on small dims):

      * matrix-unit time — ``passes`` MXU passes per logical matmul
        (``vpu``: one fp32 pass on the vector unit);
      * HBM time — A re-streamed per n-tile, B per m-tile, C written once
        (staged variants move ``n_words`` bf16 words per input element and
        pay one extra pass to materialize them);
      * staging time — bytes through the staging tier per the variant's
        data flow (paper §4.4: fused reads the fp32 source once; staged
        writes and reads back every split word).

    ``staged`` serializes the word round-trip against the MXU passes
    (t_mxu + t_stage); ``fused``/``staged_db``/``vpu`` overlap copy with
    compute (max of terms) — the double-buffered variant's whole point.
    """
    chip = chip or active_chip()
    bm, bn, bk = block
    mp, np_, kp = _pad_up(m, bm), _pad_up(n, bn), _pad_up(k, bk)
    flops = 2.0 * batch * mp * np_ * kp
    if variant == "vpu":
        t_mxu = flops / (chip.vector_tflops * 1e12)
    else:
        t_mxu = flops * passes / (chip.matrix_tflops * 1e12)

    in_bytes_elem = 4.0 if variant in ("fused", "vpu") else 2.0 * n_words
    b_batch = batch if rhs_batched else 1
    hbm = (batch * mp * kp * in_bytes_elem * (np_ // bn)
           + b_batch * kp * np_ * in_bytes_elem * (mp // bm)
           + batch * mp * np_ * 4.0)
    if variant in ("staged", "staged_db"):
        # Host-side split materialization: read fp32 source, write the words.
        hbm += (batch * mp * kp + b_batch * kp * np_) * (4.0 + 2.0 * n_words)
    t_hbm = hbm / (chip.hbm_gbps * 1e9)

    stage_in_elem = 4.0 if variant in ("fused", "vpu") else 2.0 * (2 * n_words)
    stage = (batch * mp * kp * stage_in_elem * (np_ // bn)
             + b_batch * kp * np_ * stage_in_elem * (mp // bm)
             # fp32 accumulator read+write per k step of every output tile
             + batch * mp * np_ * 8.0 * (kp // bk))
    t_stage = stage / (chip.staging_gbps * 1e9)

    steps = batch * (mp // bm) * (np_ // bn) * (kp // bk)
    t_over = LAUNCH_OVERHEAD_S + steps * GRID_STEP_OVERHEAD_S
    if variant == "staged":
        return max(t_hbm, t_mxu + t_stage) + t_over
    return max(t_hbm, t_mxu, t_stage) + t_over


def mma_arithmetic_intensity(n: int, in_bytes: int = 2, acc_bytes: int = 4,
                             out_bytes: Optional[int] = None,
                             n_input_words: int = 1) -> float:
    """Paper Eq. (1) generalized: AI of blocking-(n,n,n) MMA fed from staging.

    2 n^3 flops over (A + B) input words + C load + D store.
    ``n_input_words`` models TCEC: staged splits move w words per input
    (WMMA-API baseline); on-the-fly generation moves 1 fp32 word (w=1,
    in_bytes=4) regardless of pass count — the paper's footprint reduction.
    """
    if out_bytes is None:
        out_bytes = acc_bytes
    in_traffic = 2 * n * n * in_bytes * n_input_words
    acc_traffic = n * n * (acc_bytes + out_bytes)
    return (2.0 * n ** 3) / (in_traffic + acc_traffic)


def paper_eq1_ai(n: int) -> float:
    """Paper Eq. (1) result: AI = n/5.

    Note a faithfulness caveat: the equation as *printed* in the paper
    (fp16 A,B + fp32 C,D) evaluates to n/6; the stated result n/5 matches
    an FP16 D output (in=2B, C=4B, D=2B -> 10 n^2 denominator).  We
    reproduce the paper's stated n/5 and record the discrepancy here."""
    return mma_arithmetic_intensity(n, in_bytes=2, acc_bytes=4, out_bytes=2)


def staging_bound_tflops(ai: float, chip: ChipSpec) -> float:
    """Attainable TFLOP/s given AI against the staging tier."""
    return min(chip.matrix_tflops, ai * chip.staging_gbps / 1000.0)


def tcec_ai(n: int, passes: int, fragment_gen: str) -> float:
    """AI of the TCEC emulation (paper Fig. 7), flops counted as useful 2n^3.

    staged (WMMA-API baseline): each input's w split words are *written to*
    and *read back from* the staging tier (2 x w x 2B per element), and the
    register pressure of holding the staged fragments forces the fp32
    accumulator through staging too (+8B).  on_the_fly (WMMAe): the fp32
    source is read once (4B per element per input); splits and the
    accumulator live in registers.

    This accounting reproduces the paper's §4.4.2 numbers exactly on A100
    with blocking (32,32,32), fp16, 3 passes: 52.0 TFlop/s (WMMA-only)
    vs min(312/3, AI*bw) = 104.0 TFlop/s (WMMAe).
    """
    n_words = {1: 1, 3: 2, 6: 3, 9: 3}[passes]
    if fragment_gen == "staged":
        in_traffic = 2 * n * n * (2 * n_words * 2)   # write + read, 2B words
        acc_traffic = 2 * n * n * 4                  # C in + D out staged
    else:
        in_traffic = 2 * n * n * 4                   # fp32 source read once
        acc_traffic = 0                              # acc stays in registers
    return (2.0 * n ** 3) / (in_traffic + acc_traffic)


def tcec_attainable_tflops(n: int, passes: int, fragment_gen: str,
                           chip: ChipSpec = TPU_V5E) -> float:
    """Useful TFLOP/s of emulated FP32 GEMM (peak divided by pass count,
    as the paper divides FP16-TC peak by 3)."""
    useful_peak = chip.matrix_tflops / passes
    ai = tcec_ai(n, passes, fragment_gen)
    return min(useful_peak, ai * chip.staging_gbps / 1000.0)


def bf_ratio(chip: ChipSpec) -> Dict[str, float]:
    """Bytes-per-Flop ratios (paper §3 Table-1 analysis)."""
    return {
        "staging_vs_matrix": chip.staging_gbps / (chip.matrix_tflops * 1000.0),
        "hbm_vs_vector": chip.hbm_gbps / (chip.vector_tflops * 1000.0),
        "hbm_vs_matrix": chip.hbm_gbps / (chip.matrix_tflops * 1000.0),
    }


# ---------------------------------------------------------------------------
# Cluster-level three-term roofline (EXPERIMENTS.md §Roofline).
# ---------------------------------------------------------------------------

@dataclasses.dataclass
class RooflineTerms:
    compute_s: float
    memory_s: float
    collective_s: float

    @property
    def dominant(self) -> str:
        vals = {"compute": self.compute_s, "memory": self.memory_s,
                "collective": self.collective_s}
        return max(vals, key=vals.get)

    @property
    def bound_s(self) -> float:
        return max(self.compute_s, self.memory_s, self.collective_s)

    @property
    def roofline_fraction(self) -> float:
        """compute_s / max(term): 1.0 == perfectly compute-bound."""
        b = self.bound_s
        return self.compute_s / b if b > 0 else 0.0


def cluster_roofline(hlo_flops: float, hlo_bytes: float, collective_bytes: float,
                     n_chips: int, chip: ChipSpec = TPU_V5E,
                     links_per_chip: int = 4) -> RooflineTerms:
    """The three terms, in seconds, per the assignment's formulas."""
    compute_s = hlo_flops / (n_chips * chip.matrix_tflops * 1e12)
    memory_s = hlo_bytes / (n_chips * chip.hbm_gbps * 1e9)
    collective_s = collective_bytes / (
        n_chips * links_per_chip * chip.ici_gbps_per_link * 1e9)
    return RooflineTerms(compute_s, memory_s, collective_s)


def model_flops(n_params: float, n_tokens: float, training: bool = True) -> float:
    """6*N*D for training; 2*N*D for a forward/decode pass."""
    return (6.0 if training else 2.0) * n_params * n_tokens
