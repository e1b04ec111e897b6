"""Three-term roofline from a compiled dry-run artifact.

TPU v5e constants (per chip): 197 TFLOP/s bf16, 819 GB/s HBM, ~50 GB/s/link
ICI.  ``cost_analysis()`` of a partitioned executable reports the
*per-device* program, so the terms are:

    compute    = flops_per_device / 197e12
    memory     = hbm_bytes_per_device / 819e9
    collective = link_bytes_per_device / 50e9

MODEL_FLOPS uses the classic 6·N·D (train) / 2·N·D (inference) with
N = active params for MoE; the ratio MODEL_FLOPS / (HLO flops × chips)
surfaces remat and dispatch overheads.  Analytic corrections for FLOPs that
hide inside ``lax.scan`` loops (sLSTM) are added by the caller via
``extra_flops``.
"""

from __future__ import annotations

from dataclasses import dataclass


@dataclass(frozen=True)
class DevicePeaks:
    """Published per-chip peaks of one accelerator kind."""

    bf16_flops: float  # FLOP/s
    hbm_bytes_per_s: float
    ici_bytes_per_s: float  # per link
    source: str


# Keyed by ``jax.Device.device_kind``.  A device that is not here has no
# peak: callers report nothing for it rather than borrow another's.
DEVICE_PEAKS: dict[str, DevicePeaks] = {
    "TPU v5 lite": DevicePeaks(
        bf16_flops=197e12,
        hbm_bytes_per_s=819e9,
        # 1,600 Gbit/s of interchip interconnect over four links
        ici_bytes_per_s=50e9,
        source="Google Cloud documentation, 'TPU v5e' system architecture",
    ),
}


def device_peaks(device_kind: str | None) -> DevicePeaks | None:
    """Peaks of ``device_kind`` from :data:`DEVICE_PEAKS`, or ``None``."""
    return DEVICE_PEAKS.get(device_kind or "")


# The dry-run's three-term model targets the v5e production mesh.
_V5E = DEVICE_PEAKS["TPU v5 lite"]
PEAK_FLOPS = _V5E.bf16_flops  # bf16 / chip
HBM_BW = _V5E.hbm_bytes_per_s  # bytes/s / chip
ICI_BW = _V5E.ici_bytes_per_s  # bytes/s / link


@dataclass(frozen=True)
class Roofline:
    compute_s: float
    memory_s: float
    collective_s: float
    flops_per_device: float
    hbm_bytes_per_device: float
    link_bytes_per_device: float
    model_flops_global: float
    chips: int

    @property
    def dominant(self) -> str:
        terms = {
            "compute": self.compute_s,
            "memory": self.memory_s,
            "collective": self.collective_s,
        }
        return max(terms, key=terms.get)

    @property
    def bound_s(self) -> float:
        """Lower-bound step time = max of the three overlap-able terms."""
        return max(self.compute_s, self.memory_s, self.collective_s)

    @property
    def useful_flops_fraction(self) -> float:
        """MODEL_FLOPS / compiled FLOPs (global)."""
        total = self.flops_per_device * self.chips
        return self.model_flops_global / total if total else 0.0

    @property
    def mfu_bound(self) -> float:
        """Model-FLOPs utilisation if the step ran at the roofline bound."""
        if self.bound_s == 0:
            return 0.0
        return self.model_flops_global / (self.chips * PEAK_FLOPS * self.bound_s)

    def as_dict(self) -> dict:
        return {
            "compute_s": self.compute_s,
            "memory_s": self.memory_s,
            "collective_s": self.collective_s,
            "dominant": self.dominant,
            "bound_s": self.bound_s,
            "flops_per_device": self.flops_per_device,
            "hbm_bytes_per_device": self.hbm_bytes_per_device,
            "link_bytes_per_device": self.link_bytes_per_device,
            "model_flops_global": self.model_flops_global,
            "useful_flops_fraction": self.useful_flops_fraction,
            "mfu_bound": self.mfu_bound,
            "chips": self.chips,
        }


def roofline(
    *,
    flops_per_device: float,
    hbm_bytes_per_device: float,
    link_bytes_per_device: float,
    model_flops_global: float,
    chips: int,
) -> Roofline:
    return Roofline(
        compute_s=flops_per_device / PEAK_FLOPS,
        memory_s=hbm_bytes_per_device / HBM_BW,
        collective_s=link_bytes_per_device / ICI_BW,
        flops_per_device=flops_per_device,
        hbm_bytes_per_device=hbm_bytes_per_device,
        link_bytes_per_device=link_bytes_per_device,
        model_flops_global=model_flops_global,
        chips=chips,
    )


def model_flops(cfg, shape) -> float:
    """6·N_active·tokens (train) or 2·N_active·tokens (serve)."""
    n = cfg.active_param_count()
    if shape.kind == "train":
        tokens = shape.seq_len * shape.global_batch
        return 6.0 * n * tokens
    if shape.kind == "prefill":
        tokens = shape.seq_len * shape.global_batch
        return 2.0 * n * tokens
    # decode: one token per sequence
    return 2.0 * n * shape.global_batch


def slstm_extra_flops(cfg, shape) -> float:
    """Analytic FLOPs hidden in the sLSTM lax.scan (cost_analysis counts the
    while body once).  Per step: 4 recurrent matmuls (2·d² each) + ~20·d
    elementwise, per token, per sLSTM layer."""
    n_slstm = sum(1 for mix, _ in cfg.layer_seq() if mix == "slstm")
    if n_slstm == 0:
        return 0.0
    if shape.kind == "decode":
        tokens = shape.global_batch
    else:
        tokens = shape.seq_len * shape.global_batch
    per_token_layer = 4 * 2 * cfg.d_model**2 + 20 * cfg.d_model
    # scan body counted once by cost_analysis → missing (T-1)/T ≈ all of it
    return float(n_slstm) * tokens * per_token_layer
