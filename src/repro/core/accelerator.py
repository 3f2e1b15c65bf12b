"""Hardware descriptions.

Two machines appear in this repo:

* :data:`MPNA_PAPER` — the ASIC of the paper (Table II/III), used by the
  faithful cycle/energy reproduction in :mod:`repro.core.perf_model`.
* :data:`TPU_V5E` — the roofline target for the JAX/Pallas framework
  (assignment constants: 197 TFLOP/s bf16, 819 GB/s HBM, ~50 GB/s/link ICI).
"""
from __future__ import annotations

from dataclasses import dataclass


@dataclass(frozen=True)
class SystolicArray:
    rows: int    # K — contraction tile held per column
    cols: int    # L — parallel filters / output channels
    # SA-FC has per-PE weight buses (weights replaced every cycle);
    # SA-CONV streams weights through the array (K-cycle refill),
    # hidden by the double-buffer register after the first tile.
    dedicated_weight_buses: bool = False

    @property
    def macs_per_cycle(self) -> int:
        return self.rows * self.cols


@dataclass(frozen=True)
class MPNAConfig:
    """Paper Table II."""
    sa_conv: SystolicArray = SystolicArray(8, 8, dedicated_weight_buses=False)
    sa_fc: SystolicArray = SystolicArray(8, 8, dedicated_weight_buses=True)
    spm_bytes: int = 256              # per accumulation sub-unit
    weight_buffer_bytes: int = 36 * 1024
    data_buffer_bytes: int = 256 * 1024
    dram_bandwidth: float = 12.8e9    # B/s   [16]
    frequency: float = 280e6          # Hz
    weight_bytes: int = 1             # 8-bit fixed point
    act_bytes: int = 1
    # published physical numbers (28 nm synthesis) — used as constants, we
    # do not re-synthesize; see DESIGN.md §7.
    power_w: float = 0.239
    area_mm2: float = 2.34

    @property
    def dram_bytes_per_cycle(self) -> float:
        return self.dram_bandwidth / self.frequency


#: Energy per operation class, pJ.  Standard 28/32 nm-scaled numbers in the
#: style of Horowitz ISSCC'14 as used by Eyeriss-era accelerator papers:
#: DRAM access dominates SRAM access dominates an 8-bit MAC.
ENERGY_PJ = {
    "dram_byte": 160.0,     # ~200 pJ / 16-bit word scaled to byte granularity
    "sram_byte": 1.25,      # large on-chip buffer
    "spm_byte": 0.6,        # small scratch-pad
    "mac8": 0.2,            # 8-bit MAC @28 nm
}


@dataclass(frozen=True)
class TPUChip:
    peak_flops_bf16: float = 197e12    # FLOP/s
    hbm_bandwidth: float = 819e9       # B/s
    ici_link_bandwidth: float = 50e9   # B/s per link (per direction)
    ici_links: int = 4                 # full-duplex inter-chip links
    hbm_bytes: int = 16 * 1024**3      # v5e: 16 GiB
    vmem_bytes: int = 128 * 1024**2    # ~128 MiB VMEM
    # usable VMEM budget the dataflow planner hands to kernels
    vmem_budget: int = 96 * 1024**2

    @property
    def ridge_flops_per_byte(self) -> float:
        """Arithmetic-intensity ridge point — the SA-CONV/SA-FC dispatch
        threshold of :mod:`repro.core.engine`."""
        return self.peak_flops_bf16 / self.hbm_bandwidth   # ~240 FLOP/B

    @property
    def ici_broadcast_bandwidth(self) -> float:
        """Delivered one-to-all broadcast bandwidth of the mesh fabric.

        A long weight stream is broadcast down ``2 * ici_links``
        edge-disjoint spanning trees (each full-duplex link carries a
        distinct chunk in each direction — the standard torus-collective
        trick), so the stream is delivered at the aggregate link rate,
        not a single link's.  ~400 GB/s with the v5e defaults; still
        well under ``hbm_bandwidth``, which is why a cooperative sharded
        wave must *amortize* the one broadcast over the whole fleet
        batch to beat per-replica HBM streams (see
        :func:`repro.core.perf_model.sharded_wave_cost`)."""
        return 2 * self.ici_links * self.ici_link_bandwidth


MPNA_PAPER = MPNAConfig()
TPU_V5E = TPUChip()

#: The chips the planner has constants for, keyed by the ``device_kind``
#: JAX reports (a v5e reports ``"TPU v5 lite"``).
CHIPS: dict[str, TPUChip] = {"TPU v5 lite": TPU_V5E}


def chip_for(device_kind: str) -> TPUChip:
    """The planner's constants for a TPU ``device_kind``.  A kind with no
    entry is an error, never a default: planning one chip with another's
    VMEM and bandwidth constants would hand the compiler plans it may
    refuse."""
    try:
        return CHIPS[device_kind]
    except KeyError:
        raise ValueError(f"no planner constants for TPU device kind "
                         f"{device_kind!r}; known: {sorted(CHIPS)}") from None


def current_chip() -> TPUChip:
    """The chip this process plans for: the attached TPU's own constants,
    looked up by ``device_kind``, or :data:`TPU_V5E` — the modeled target
    — when the kernels run in the interpreter on another backend."""
    import jax

    if jax.default_backend() != "tpu":
        return TPU_V5E
    return chip_for(jax.devices()[0].device_kind)
