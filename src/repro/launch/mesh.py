"""Production mesh definitions.

Single pod: 16x16 = 256 chips, axes ("data", "model").
Multi-pod:  2x16x16 = 512 chips, axes ("pod", "data", "model") — the "pod"
axis is the hierarchical-indexing level of the paper (Core/AGG switches,
DESIGN.md §2); DCN-crossing collectives are confined to it.

Functions, not module constants: importing this module never touches jax
device state (the dry-run must set XLA_FLAGS before any jax init).
"""

from __future__ import annotations

import jax

from repro.core.dist_store import make_mesh


def make_production_mesh(*, multi_pod: bool = False):
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return make_mesh(shape, axes)


def dp_axes(mesh) -> tuple[str, ...]:
    """The data-parallel axes of a production mesh."""
    return tuple(a for a in mesh.axis_names if a in ("pod", "data"))


def make_host_mesh(n_data: int | None = None, n_model: int = 1):
    """Small mesh over however many (host) devices exist — tests/examples."""
    n = len(jax.devices())
    n_data = n_data or max(1, n // n_model)
    return make_mesh((n_data, n_model), ("data", "model"))


# Per-chip peaks keyed by ``jax.Device.device_kind``.  TPU v5e: Google
# Cloud documentation, "TPU v5e" (197 TFLOP/s bf16, 819 GB/s HBM); ICI is
# ~per-chip injection over one link.
PEAKS = {
    "TPU v5 lite": {"flops_bf16": 197e12, "hbm_bw": 819e9, "ici_bw": 50e9},
}


def device_peaks(device_kind: str) -> dict:
    """The peaks of one chip of ``device_kind``; an unknown kind raises."""
    try:
        return PEAKS[device_kind]
    except KeyError:
        raise ValueError(
            f"no peak table for device kind {device_kind!r}; "
            f"known: {sorted(PEAKS)}"
        ) from None
