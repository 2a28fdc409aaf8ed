"""Hardware table, keyed by ``jax.Device.device_kind``.

Peaks come from the Google Cloud documentation page "TPU v5e" (system
architecture): per chip 197 TFLOP/s bf16, 393 TOP/s int8, 16 GB of HBM at
819 GB/s, and 1,600 Gbit/s of inter-chip interconnect.  ``vmem_bytes`` is
the scoped VMEM a Pallas kernel may use without raising the compiler's
limit (16 MiB on v5e, as the TPU compiler reports when a kernel exceeds
it), not the chip's physical 128 MiB.

``chip(kind)`` raises for a kind that is not in the table: numbers for one
chip are never silently applied to another.  The module-level constants
are the repo's modelling target (v5e), read by the roofline cost models.
"""
from __future__ import annotations

import dataclasses


@dataclasses.dataclass(frozen=True)
class Chip:
    name: str
    peak_flops_bf16: float    # FLOP/s
    peak_ops_int8: float      # OP/s
    hbm_bytes: float
    hbm_bw: float             # bytes/s
    ici_bw: float             # bytes/s per chip, all links together
    ici_links: int            # 2D torus: +x, -x, +y, -y
    vmem_bytes: int           # default scoped VMEM limit of one kernel
    mxu_tile: int             # systolic array native tile edge
    source: str


_V5E = Chip(
    name="TPU v5e", peak_flops_bf16=197e12, peak_ops_int8=393e12,
    hbm_bytes=16e9, hbm_bw=819e9, ici_bw=1600e9 / 8, ici_links=4,
    vmem_bytes=16 * 1024 * 1024, mxu_tile=128,
    source='Google Cloud documentation, "TPU v5e"')

# jax reports a v5e chip as "TPU v5 lite"
CHIPS = {"TPU v5 lite": _V5E}


class UnknownDevice(KeyError):
    """A ``device_kind`` with no entry in :data:`CHIPS`."""


def chip(kind: str) -> Chip:
    try:
        return CHIPS[kind]
    except KeyError:
        raise UnknownDevice(
            f"no hardware entry for device kind {kind!r}; known: "
            f"{sorted(CHIPS)}") from None


TARGET = _V5E

PEAK_FLOPS_BF16 = TARGET.peak_flops_bf16
HBM_BW = TARGET.hbm_bw
ICI_LINKS_PER_CHIP = TARGET.ici_links
ICI_BW_PER_LINK = TARGET.ici_bw / TARGET.ici_links
COLL_LATENCY_S = 20e-6    # collective launch latency: modelled, not measured
VMEM_BYTES = TARGET.vmem_bytes
MXU_TILE = TARGET.mxu_tile
HBM_BYTES = TARGET.hbm_bytes

DTYPE_BYTES = {
    "float32": 4, "f32": 4,
    "bfloat16": 2, "bf16": 2,
    "float16": 2, "f16": 2,
    "int8": 1, "s8": 1, "u8": 1, "uint8": 1,
    "int32": 4, "s32": 4, "u32": 4, "uint32": 4,
    "int64": 8, "s64": 8, "u64": 8, "uint64": 8,
    "float64": 8, "f64": 8,
    "bool": 1, "pred": 1,
    "int16": 2, "s16": 2, "u16": 2, "uint16": 2,
    "float8_e4m3fn": 1, "f8e4m3fn": 1, "float8_e5m2": 1, "f8e5m2": 1,
}
