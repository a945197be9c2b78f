"""Topology declarations + the link-tier constants of the static cost
model.

This module is one half of ROADMAP item 5(b) — the deterministic
topology simulator.  It owns:

* :class:`TopologySpec` — a declared chip topology (``pods`` ×
  ``chips_per_pod``), the thing that makes a 256-chip mesh *testable on
  CPU*: the cost model evaluates a collective schedule against a spec,
  never against the devices the process happens to see.  Axis classes
  follow the mesh convention (``parallel/mesh.py``): the ``dcn`` tier
  spans pods, the ``ici`` tier spans chips within a pod.

* :class:`LinkConstants` — the per-tier (alpha, beta, gamma) terms of
  the alpha-beta model: per-hop launch/latency seconds, per-wire-byte
  seconds (inverse bandwidth), and per-logical-byte quantize/dequantize
  compute seconds for compressed wires.

* ``DEFAULT_TIER_CONSTANTS`` — order-of-magnitude fallbacks used ONLY
  when the fitted calibration file has no matching group.  Real
  constants come from :func:`analysis.costmodel.fit_from_bench` over
  measured ``bench_allreduce.py --json-out`` rows — policies are
  measured, not guessed (the ``HVDT_AUTOTUNE_*_SEED`` principle).

Single-source-of-truth contract: device peak-FLOPs/HBM numbers live in
``telemetry/step_stats.PEAK_BY_DEVICE_KIND`` (imported here, never
duplicated); link-level latency/bandwidth literals live HERE.  The
``magic-peak-flops`` lint rule (analysis/lint.py) flags hardware-rate
literals anywhere else in the package, so the MFU gauge and the cost
model can never drift apart.
"""

from __future__ import annotations

import dataclasses
import os
from typing import Dict, Optional, Sequence, Tuple

__all__ = [
    "LinkConstants", "TopologySpec", "DEFAULT_TIER_CONSTANTS",
    "TIER_ICI", "TIER_DCN", "TIERS", "classify_axis", "tier_sizes",
    "chip_peak_flops", "NOMINAL_SIM_PEAK_FLOPS",
]

TIER_ICI = "ici"
TIER_DCN = "dcn"
TIERS: Tuple[str, ...] = (TIER_ICI, TIER_DCN)


@dataclasses.dataclass(frozen=True)
class LinkConstants:
    """Alpha-beta-gamma terms for one transport tier.

    ``seconds = alpha * hops + beta * wire_bytes + gamma * logical_bytes``

    * ``alpha_s`` — per-hop latency/launch cost (the latency term a
      tree algorithm minimises);
    * ``beta_s_per_byte`` — per-wire-byte transfer cost, i.e. inverse
      link bandwidth (the term a ring algorithm minimises);
    * ``gamma_s_per_byte`` — per-logical-byte quantize/dequantize
      compute charged by compressed wires (0 for f32).
    """

    alpha_s: float
    beta_s_per_byte: float
    gamma_s_per_byte: float = 0.0

    def to_dict(self) -> Dict[str, float]:
        return {"alpha_s": self.alpha_s,
                "beta_s_per_byte": self.beta_s_per_byte,
                "gamma_s_per_byte": self.gamma_s_per_byte}

    @classmethod
    def from_dict(cls, d: Dict[str, float]) -> "LinkConstants":
        return cls(alpha_s=float(d.get("alpha_s", 0.0)),
                   beta_s_per_byte=float(d.get("beta_s_per_byte", 0.0)),
                   gamma_s_per_byte=float(d.get("gamma_s_per_byte", 0.0)))


# Fallback tier constants — public TPU-generation order-of-magnitude
# figures (ICI: ~100 GB/s per link, ~1 us hop; DCN: ~25 GB/s per host,
# ~10 us hop).  The fitted calibration always wins; these only keep the
# model total when a (tier, algorithm, wire) group was never measured.
DEFAULT_TIER_CONSTANTS: Dict[str, LinkConstants] = {
    TIER_ICI: LinkConstants(alpha_s=1.0e-6,
                            beta_s_per_byte=1.0 / 100.0e9),
    TIER_DCN: LinkConstants(alpha_s=10.0e-6,
                            beta_s_per_byte=1.0 / 25.0e9),
}

# The magic-peak-flops lint rule's classification window: numeric
# literals in [floor, ceil] look like hardware rates (the table above
# spans 46e12..2765e9; nothing real exceeds 1e16 yet) — masking
# sentinels like -1e30 and unit conversions like 1e9 fall outside.
# The rule imports these so its bounds live where the constants do.
PEAK_LITERAL_FLOOR = 1e11
PEAK_LITERAL_CEIL = 1e16

# Nominal peak FLOP/s when no real device kind matches the table (CPU
# simulator) — the HVDT_PEAK_FLOPS default and report_pipeline_mfu
# fallback.  Any consistent value works there (MFU is a ratio); it
# lives HERE so the magic-peak-flops rule keeps it single-sourced.
NOMINAL_SIM_PEAK_FLOPS = 1e12

# Per-logical-byte quantize/dequantize fallback for compressed wires
# (block-scaled int8/int4 kernels run near HBM speed — the packed int4
# wire pays the same per-element pass plus the nibble pack/unpack;
# bf16/fp16 casts are cheaper still).  Fitted gamma from quantized-wire
# bench rows overrides.
DEFAULT_QUANT_GAMMA_S_PER_BYTE: Dict[str, float] = {
    "int8": 1.0 / 400.0e9,
    "int4": 1.0 / 400.0e9,
    "bf16": 1.0 / 800.0e9,
    "fp16": 1.0 / 800.0e9,
}


# Reference per-chip step workload for scaling curves (ResNet-50 at
# batch 128: 25.6M f32 params -> ~102 MB of gradients, and
# XLA's cost-analysis flops of that step).  Living here keeps the
# curve's magnitudes out of the magic-peak-flops window elsewhere.
REFERENCE_STEP_WORKLOAD: Dict[str, float] = {
    "grad_bytes": 102.4e6,
    "flops_per_step": 2.164e11,
}


@dataclasses.dataclass(frozen=True)
class TopologySpec:
    """A declared chip topology the model evaluates schedules against.

    ``TopologySpec(pods=16, chips_per_pod=16)`` is a 256-chip mesh —
    evaluable on a 1-CPU container, which is the point.  ``device_kind``
    keys the compute-side peak table
    (``telemetry/step_stats.PEAK_BY_DEVICE_KIND``) for scaling curves
    that need a compute term next to the comm term.
    """

    pods: int = 1
    chips_per_pod: int = 8
    device_kind: str = "v5 lite"

    def __post_init__(self):
        if self.pods < 1 or self.chips_per_pod < 1:
            raise ValueError(
                f"TopologySpec needs pods >= 1 and chips_per_pod >= 1, "
                f"got pods={self.pods} chips_per_pod={self.chips_per_pod}")

    @property
    def total_chips(self) -> int:
        return self.pods * self.chips_per_pod

    def tier_size(self, tier: str) -> int:
        """Extent of one transport tier: ``dcn`` spans pods, ``ici``
        spans chips within a pod."""
        if tier == TIER_DCN:
            return self.pods
        if tier == TIER_ICI:
            return self.chips_per_pod
        raise ValueError(f"unknown tier {tier!r}; valid: {TIERS}")

    def describe(self) -> str:
        return (f"{self.pods}x{self.chips_per_pod} "
                f"({self.total_chips} chips, {self.device_kind})")

    @classmethod
    def from_env(cls, default: Optional["TopologySpec"] = None
                 ) -> "TopologySpec":
        """Topology from the elastic launcher's pod contract
        (``HVDT_NUM_PODS`` contract var + ``HVDT_POD_SIZE`` knob), else
        ``default`` (a single 8-chip pod)."""
        default = default or cls()
        try:
            pods = int(os.environ.get("HVDT_NUM_PODS", "") or 0)
            chips = int(os.environ.get("HVDT_POD_SIZE", "") or 0)
        except ValueError:
            return default
        if pods >= 1 and chips >= 1:
            return cls(pods=pods, chips_per_pod=chips,
                       device_kind=default.device_kind)
        return default

    def to_dict(self) -> Dict[str, object]:
        return {"pods": self.pods, "chips_per_pod": self.chips_per_pod,
                "device_kind": self.device_kind}


def classify_axis(axis: str, axes: Sequence[str]) -> str:
    """Transport tier of one mesh axis within its reduce group.

    Literal ``ici``/``dcn`` names classify themselves (the pod mesh
    contract names its axes exactly that); the 4D pod axes follow the
    ``pod_mesh_spec`` placement contract — ``pp`` carves whole pod
    groups (its ppermute ticks cross DCN), ``ep`` carves chips inside a
    pod (its expert a2a rides ICI); anything else falls back to the
    ``parallel/mesh.py`` position convention — innermost axis rides
    ICI, outer axes cross DCN."""
    if axis in TIERS:
        return axis
    from ..parallel import mesh as _mesh

    if axis == _mesh.AXIS_PP:
        return TIER_DCN
    if axis == _mesh.AXIS_EP:
        return TIER_ICI
    return _mesh.axis_transport_class(axis, axes)


def tier_sizes(axes: Sequence[str], topo: TopologySpec
               ) -> Dict[str, int]:
    """Per-tier group extents for a reduce group on ``topo``: every
    axis contributes its tier's declared extent (multi-axis tiers
    multiply, matching a (pipe, dp)-style stacked dcn extent)."""
    sizes: Dict[str, int] = {}
    for ax in axes:
        tier = classify_axis(ax, axes)
        sizes[tier] = sizes.get(tier, 1) * topo.tier_size(tier)
    return sizes


def chip_peak_flops(device_kind: str) -> Optional[float]:
    """Per-chip bf16 peak FLOP/s from the ONE peak table
    (``telemetry/step_stats.peak_flops_for``) — never a literal here."""
    from ..telemetry.step_stats import peak_flops_for

    flops, _ = peak_flops_for(device_kind)
    return flops
