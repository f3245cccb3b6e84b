"""Peak rates of the chips the roofline knows, keyed by JAX ``device_kind``.

Source: Google Cloud documentation, "TPU v5e" (197 TFLOP/s bf16, 16 GB HBM
at 819 GB/s, 1,600 Gbit/s of inter-chip interconnect per chip over four
links). A device kind missing from :data:`CHIPS` is an error, never a
default: a roofline share against another chip's peaks is meaningless.
"""
import dataclasses


@dataclasses.dataclass(frozen=True)
class Chip:
    name: str
    peak_flops_bf16: float     # FLOP/s
    hbm_bw: float              # B/s
    ici_bw_per_link: float     # B/s per link
    ici_links: int             # usable links per chip (2D torus: 4)
    hbm_bytes: float


TPU_V5E = Chip(
    name="tpu_v5e",
    peak_flops_bf16=197e12,
    hbm_bw=819e9,
    ici_bw_per_link=50e9,
    ici_links=4,
    hbm_bytes=16e9,
)

#: ``jax.devices()[0].device_kind`` -> peaks
CHIPS = {"TPU v5 lite": TPU_V5E}


def chip_for(device_kind: str) -> Chip:
    """Peaks of ``device_kind``; raises ``KeyError`` for an unknown kind."""
    try:
        return CHIPS[device_kind]
    except KeyError:
        raise KeyError(f"no peak rates for device kind {device_kind!r}; "
                       f"known: {sorted(CHIPS)}") from None
