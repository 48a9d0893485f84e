"""Hardware resource model — the TPU analogue of the paper's FPGA constants.

The paper constrains its NLP with per-SLR DSP budgets, BRAM capacity and a
maximum array-partitioning factor (Eqs. 7-11).  On TPU the corresponding
budget terms are:

    DSP budget        -> MXU peak FLOP rate per chip (de-rated by alignment)
    BRAM capacity     -> VMEM bytes per core
    max partitioning  -> vector lane geometry (8 sublanes x 128 lanes)
    off-chip bitwidth -> HBM bandwidth (bytes/s) with lane-packing efficiency
    inter-SLR routing -> ICI link bandwidth between slices / pods

``Slice`` is the SLR analogue: a physically distinct resource region that a
task is assigned to (``slr_t`` in the paper, Eq. 11).  A slice may be one chip
(the default for PolyBench-scale task graphs, mirroring "1 SLR") or a mesh
sub-slice / pod for LM-scale placement.
"""
from __future__ import annotations

import dataclasses
from typing import Sequence

# ---------------------------------------------------------------------------
# Roofline constants of one TPU v5e chip (Google Cloud documentation,
# "TPU v5e": 197 TFLOP/s bf16, 16 GB HBM at 819 GB/s)
# ---------------------------------------------------------------------------
#: ``jax.Device.device_kind`` of the chip these constants describe; a TPU
#: of any other kind must not be priced with them.
TPU_KINDS = ("TPU v5 lite", "TPU v5e")
PEAK_FLOPS_BF16 = 197e12          # FLOP/s per chip
HBM_BW = 819e9                    # bytes/s per chip
ICI_BW = 50e9                     # bytes/s per link
# Scoped VMEM per kernel: the solver's capacity constraint AND the
# ``vmem_limit_bytes`` every Pallas kernel is compiled with
# (``repro.kernels.dispatch.compiler_params``) — one number for both.
VMEM_BYTES = 16 * 2 ** 20
VMEM_BW = 20 * HBM_BW             # on-chip buffer handoff bandwidth (VMEM)
CLOCK_HZ = 940e6                  # nominal core clock (latency-term conversion)

# MXU / VPU geometry: the "max array partitioning" analogue.  A block whose
# trailing dim is a multiple of LANE and second-to-last a multiple of SUBLANE
# issues at full rate; misaligned blocks are padded by the hardware and the
# padded fraction is wasted.
LANE = 128
SUBLANE = 8

# Fixed per-grid-step overhead (DMA issue + pipeline bubble), in seconds.
# Plays the role of the paper's iteration-latency constants IL_par / IL_red.
STEP_OVERHEAD_S = 120 / CLOCK_HZ
# Extra cycles to drain a reduction tree of depth log2(n) (Eq. 15 analogue).
RED_LATENCY_S = 6 / CLOCK_HZ


def alignment_efficiency(block: Sequence[int]) -> float:
    """Fraction of MXU/VPU issue slots doing useful work for a VMEM block.

    The paper models unroll efficiency via DSP counts of the fully unrolled
    intra-tile (Eq. 10); on TPU the analogous de-rating is the lane/sublane
    padding of the last two block dims.  A (m, 190) block issues as (m, 256)
    -> efficiency 190/256.
    """
    if not block:
        return 1.0
    dims = list(block)
    eff = 1.0
    last = dims[-1]
    eff *= last / _round_up(last, LANE)
    if len(dims) >= 2:
        sub = dims[-2]
        eff *= sub / _round_up(sub, SUBLANE)
    return eff


def _round_up(x: int, m: int) -> int:
    return ((x + m - 1) // m) * m


def packing_efficiency(last_dim_elems: int, dtype_bytes: int) -> float:
    """HBM burst efficiency for a transfer whose minor dim is ``last_dim_elems``.

    FPGA analogue: data packing into <=512-bit bursts (paper §2.1.6) — a
    transfer whose row size is not a multiple of the burst width wastes
    bandwidth.  TPU DMAs move (8, 128)-element granules; a row of
    ``last_dim_elems`` occupies ceil(n/128) granule rows.
    """
    row_bytes = last_dim_elems * dtype_bytes
    granule = LANE * dtype_bytes
    padded = _round_up(max(row_bytes, 1), granule)
    return row_bytes / padded


# A "board" (chip) exposes SLICES — the SLR analogue.  Like SLRs on an SSI
# device, slices are physically distinct COMPUTE regions (TPU cores /
# MXU groups) that SHARE the off-chip memory system: placing a design on
# more slices multiplies compute and VMEM but NOT HBM bandwidth — exactly
# the paper's multi-SLR economics (compute-bound kernels scale, memory-
# bound ones don't; Table 8).  The board has BOARD_SLICES regions.
BOARD_SLICES = 3


@dataclasses.dataclass(frozen=True)
class Slice:
    """An SLR analogue: one compute region of the board."""

    sid: int
    chips: int = 1
    # Budget fractions mirror the paper's per-SLR utilisation targets
    # (e.g. "60% of one SLR" in the on-board evaluation).
    compute_frac: float = 1.0
    vmem_frac: float = 1.0
    # Board-level rates this slice divides — overridden by calibration
    # (repro.calibrate) with rates measured on the running host.
    board_flops: float = PEAK_FLOPS_BF16
    board_hbm_bw: float = HBM_BW

    @property
    def flops(self) -> float:
        """Peak of ONE region = board peak / BOARD_SLICES."""
        return self.board_flops / BOARD_SLICES * self.chips \
            * self.compute_frac

    @property
    def hbm_bw(self) -> float:
        """A single active region can saturate the full HBM system; the
        schedule-level share (per-wave active slices) is applied by the
        cost model (plan_latency) — DRAM channels are a board resource."""
        return self.board_hbm_bw * self.chips

    @property
    def vmem(self) -> float:
        return VMEM_BYTES * self.vmem_frac


@dataclasses.dataclass(frozen=True)
class Hardware:
    """Board-level description: a set of slices plus interconnect.

    The rate fields default to the static TPU-v5e constants above; a
    calibrated board (``repro.calibrate.CalibratedHardware.hardware()``)
    replaces them with rates *measured on the running host*, including two
    terms the static model has no number for:

    * ``dispatch_s`` — fixed per-task host dispatch overhead.  Tasks on the
      same slice serialize their dispatches; tasks on different slices
      overlap them, so this is exactly the "dispatch saving" the solver
      weighs against cross-slice stream cost.
    * ``hbm_share`` — measured per-slice fraction of solo HBM bandwidth
      when ``k`` slices are concurrently active (index ``k-1``).  Real
      memory systems de-rate more gracefully than the analytic ``1/k``.
    """

    slices: tuple[Slice, ...]
    ici_bw: float = ICI_BW       # bytes/s between slices (FIFO/stream analogue)
    hbm_bw: float = HBM_BW       # bytes/s off-chip, shared across slices
    vmem: float = VMEM_BYTES
    peak_flops: float = PEAK_FLOPS_BF16
    dispatch_s: float = 0.0      # per-task dispatch overhead (calibrated)
    hbm_share: tuple[float, ...] | None = None   # measured share curve

    @staticmethod
    def make(n_slices: int = 1, chips_per_slice: int = 1,
             compute_frac: float = 1.0, vmem_frac: float = 1.0,
             peak_flops: float = PEAK_FLOPS_BF16, hbm_bw: float = HBM_BW,
             ici_bw: float = ICI_BW, dispatch_s: float = 0.0,
             hbm_share: tuple[float, ...] | None = None) -> "Hardware":
        return Hardware(
            slices=tuple(
                Slice(sid=i, chips=chips_per_slice,
                      compute_frac=compute_frac, vmem_frac=vmem_frac,
                      board_flops=peak_flops, board_hbm_bw=hbm_bw)
                for i in range(n_slices)),
            ici_bw=ici_bw, hbm_bw=hbm_bw, peak_flops=peak_flops,
            dispatch_s=dispatch_s, hbm_share=hbm_share)

    @property
    def n_slices(self) -> int:
        return len(self.slices)

    def fingerprint(self) -> str:
        """Content hash of every rate the cost model prices with — the
        plan store's hardware key.  Calibration drift (new measured
        rates) changes this, which is what invalidates stored plans."""
        from .fingerprint import hardware_fingerprint
        return hardware_fingerprint(self)

    def bw_share_at(self, n_active: int) -> float:
        """Per-slice fraction of solo HBM bandwidth when ``n_active``
        slices are concurrently active in the same wave.  Uses the
        measured share curve when calibrated, the analytic ``1/n``
        split otherwise."""
        n = max(int(n_active), 1)
        if self.hbm_share:
            return self.hbm_share[min(n, len(self.hbm_share)) - 1]
        return 1.0 / n


# Canonical boards used by benchmarks (Table 8 analogue: "1 SLR" vs "3 SLR").
ONE_SLICE = Hardware.make(n_slices=1)
THREE_SLICE = Hardware.make(n_slices=3)
# 60%-utilisation variants (the paper's on-board constraint scenario).
ONE_SLICE_60 = Hardware.make(n_slices=1, compute_frac=0.6, vmem_frac=0.6)
THREE_SLICE_60 = Hardware.make(n_slices=3, compute_frac=0.6, vmem_frac=0.6)
