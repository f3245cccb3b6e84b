"""Peak rates of the chips the benchmark knows, keyed by JAX ``device_kind``.

Source: Google Cloud documentation, "TPU v5e": 197 TFLOP/s in bf16,
393 TOP/s in int8, 16 GB of HBM at 819 GB/s, 1,600 Gbit/s of chip-to-chip
interconnect. A device kind missing from :data:`CHIPS` is an error, never a
default: a share of another chip's peak means nothing.
"""
import dataclasses


@dataclasses.dataclass(frozen=True)
class Chip:
    name: str
    flops_bf16: float     # FLOP/s
    hbm_bw: float         # B/s
    hbm_bytes: float


CHIPS = {
    "TPU v5 lite": Chip(name="tpu_v5e", flops_bf16=197e12, hbm_bw=819e9,
                        hbm_bytes=16e9),
}


def chip_for(device_kind: str) -> Chip:
    try:
        return CHIPS[device_kind]
    except KeyError:
        raise KeyError(f"no peak rates for device kind {device_kind!r}; "
                       f"known: {sorted(CHIPS)}") from None
