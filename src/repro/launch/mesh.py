"""Production mesh definitions.

Defined as FUNCTIONS (never module-level constants) so importing this module
never touches jax device state. The dry-run entrypoint sets
XLA_FLAGS=--xla_force_host_platform_device_count=<n> BEFORE importing jax.
"""
from __future__ import annotations


def make_production_mesh(*, multi_pod: bool = False, pipeline_stages: int = 1):
    """The production device mesh. ``pipeline_stages >= 2`` carves a
    ``stage`` axis out of the data axis (stages are contiguous device blocks
    inside what would otherwise be data slices, keeping the high-traffic
    model axis innermost); the data-axis size must divide evenly."""
    import numpy as np

    import jax

    from repro import compat

    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    if pipeline_stages > 1:
        di = axes.index("data")
        if shape[di] % pipeline_stages:
            raise ValueError(
                f"data axis {shape[di]} not divisible by "
                f"pipeline_stages={pipeline_stages}"
            )
        shape = (shape[:di] + (shape[di] // pipeline_stages, pipeline_stages)
                 + shape[di + 1:])
        axes = axes[:di + 1] + ("stage",) + axes[di + 1:]
    n = int(np.prod(shape))
    devices = jax.devices()
    if len(devices) < n:
        raise RuntimeError(
            f"need {n} devices for mesh {shape}, have {len(devices)} — set "
            "XLA_FLAGS=--xla_force_host_platform_device_count before importing jax"
        )
    return compat.make_mesh(shape, axes, devices=devices[:n])


def required_device_count(multi_pod: bool) -> int:
    return 512 if multi_pod else 256


# TPU v5e hardware constants used by the roofline analysis (§Roofline)
PEAK_FLOPS_BF16 = 197e12       # per chip
HBM_BW = 819e9                 # bytes/s per chip
ICI_BW_PER_LINK = 50e9         # bytes/s per link (we report per-link terms)
HBM_PER_CHIP = 16 * 2 ** 30
