"""Target hardware model: TPU v5e (one chip) + ICI mesh.

Single source of truth for every roofline / DSE / block-selection constant.
They describe one device kind only: code that relies on them on a real
chip calls :func:`require_target_device` first.
"""

# ``jax.devices()[0].device_kind`` of the chip these constants describe
TARGET_DEVICE_KIND = "TPU v5 lite"

# --- per-chip compute / memory -------------------------------------------
PEAK_FLOPS_BF16 = 197e12          # FLOP/s
PEAK_FLOPS_FP32 = PEAK_FLOPS_BF16 / 4
HBM_BYTES = 16 * 2 ** 30          # 16 GiB
HBM_BW = 819e9                    # B/s
# VMEM per core as the v5e compiler reports it ("would exceed memory
# (size=134217728)"); tests/test_tpu_compile.py checks the figure.
VMEM_BYTES = 128 * 2 ** 20
# What the fit models may spend, and the ``vmem_limit_bytes`` every TT
# kernel is compiled with: half of VMEM, the rest left to Mosaic's own
# internal scratch.
VMEM_BUDGET_BYTES = 64 * 2 ** 20

# --- vector/matrix unit geometry ------------------------------------------
MXU = 128                         # systolic array dim
LANES = 128
SUBLANES = 8

# --- interconnect ----------------------------------------------------------
ICI_BW = 50e9                     # B/s per link (prompt-specified)

# --- mesh ------------------------------------------------------------------
POD_CHIPS = 256                   # 16 x 16 single pod
NUM_PODS = 2


def require_target_device(device_kind: str) -> None:
    """Raise unless ``device_kind`` is the chip these constants describe."""
    if device_kind != TARGET_DEVICE_KIND:
        raise RuntimeError(
            f"core/hw.py describes a {TARGET_DEVICE_KIND!r} chip, but this "
            f"device reports {device_kind!r}; its VMEM and peak figures "
            f"would be wrong here")


def ridge_intensity(dtype_bytes: int = 2) -> float:
    """FLOP/byte at which compute and HBM terms balance."""
    return PEAK_FLOPS_BF16 / HBM_BW


def compute_seconds(flops: float, chips: int = 1) -> float:
    return flops / (chips * PEAK_FLOPS_BF16)


def memory_seconds(bytes_: float, chips: int = 1) -> float:
    return bytes_ / (chips * HBM_BW)


def collective_seconds(bytes_: float, chips: int = 1) -> float:
    return bytes_ / (chips * ICI_BW)
