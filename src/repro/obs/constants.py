"""THE roofline constants: peaks per device kind, overridable by measurement.

Every modeled time in this repo — the planner's dense/ECR/PECR/BSR
arbitration (`repro.graph.registry.unit_model_us`), the autotuner's
noisy-clock fallback (`repro.serving.autotune.plan_model_us`) and the
calibration fits (`repro.obs.calibrate`, `repro.obs.tilesearch`) — divides
FLOPs and HBM bytes by the pair `device_peaks()` returns for the device the
process runs on.

`DEVICE_PEAKS` is keyed by `jax.Device.device_kind` and holds published
datasheet peaks, not what the Pallas kernels achieve;
`repro.obs.calibrate.CalibrationDB` fits per-(device kind, op kind, impl,
block geometry) EFFECTIVE constants from measured kernel time and overrides
these wherever a cost is modeled. A TPU whose kind is not in the table is
an error, never priced as another chip. Any other platform (the CPU the
tests run on) is priced at `CPU_TEST_PRIOR`: the v5e pair, a stand-in that
keeps CPU-side plans and tests deterministic — never a CPU measurement.

This module must stay dependency-free (stdlib only, jax imported lazily):
it sits below the op registry in the import graph.
"""
from __future__ import annotations

from dataclasses import dataclass


@dataclass(frozen=True)
class RooflineConstants:
    """One (compute ceiling, memory ceiling) pair — published or calibrated."""

    peak_flops: float  # FLOP/s
    hbm_bw: float  # B/s

    def time_us(self, flops: float, nbytes: float) -> float:
        """Roofline time (us): max of the compute and memory terms."""
        return max(flops / self.peak_flops, nbytes / self.hbm_bw) * 1e6

    def scaled(self, s: float) -> "RooflineConstants":
        """Both ceilings scaled by efficiency `s` (the CalibrationDB's fit:
        a kernel running at fraction `s` of the datasheet roofline)."""
        return RooflineConstants(self.peak_flops * s, self.hbm_bw * s)


DEVICE_PEAKS = {
    # TPU v5e, one chip (Google Cloud documentation, "TPU v5e"): 197 TFLOP/s
    # bf16, 819 GB/s HBM. JAX reports the chip as "TPU v5 lite".
    "TPU v5 lite": RooflineConstants(peak_flops=197e12, hbm_bw=819e9),
}

# the prior every non-TPU process prices layers at (see module docstring)
CPU_TEST_PRIOR = DEVICE_PEAKS["TPU v5 lite"]


def peaks_for(device_kind: str, platform: str) -> RooflineConstants:
    """The published peaks of `device_kind`; `CPU_TEST_PRIOR` off the TPU.
    Raises KeyError for a TPU kind the table does not hold."""
    if device_kind in DEVICE_PEAKS:
        return DEVICE_PEAKS[device_kind]
    if platform == "tpu":
        raise KeyError(f"no published peaks for TPU kind {device_kind!r}: "
                       "add it to repro.obs.constants.DEVICE_PEAKS")
    return CPU_TEST_PRIOR


def device_peaks() -> RooflineConstants:
    """`peaks_for` the first device of this process."""
    import jax

    dev = jax.devices()[0]
    return peaks_for(dev.device_kind, dev.platform)
