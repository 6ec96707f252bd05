"""The device a run measures: the chip check, the peak table, memory.

No chip, no numbers: a run that finds no TPU, or fewer chips than its
cell asks for, raises ``NoChip`` before any work, and ``run.py`` exits
non-zero without a result line.
"""
from __future__ import annotations

# Published per-chip peaks, keyed by ``device_kind`` as JAX reports it.
# f32 matmuls at JAX's default precision run as bf16 passes on the MXU,
# so the bf16 rate is the FLOP peak for every program measured here.
PEAKS = {
    "TPU v5 lite": {
        "flops_per_s": 197e12,
        "hbm_bytes_per_s": 819e9,
        "hbm_bytes": 16e9,
        "source": "Google Cloud documentation, 'TPU v5e': 197 TFLOP/s "
                  "bf16, 819 GB/s HBM bandwidth, 16 GB HBM per chip",
    },
}


class NoChip(RuntimeError):
    """No accelerator, or fewer chips than the cell needs."""


def peaks(kind: str) -> dict:
    """-> the peak row of ``kind``; KeyError for a device not in the table
    (an unknown device is an error, never a default)."""
    if kind not in PEAKS:
        raise KeyError(f"no peak figures for device kind {kind!r}; add a "
                       f"row with its source to bench/lib/device.py")
    return PEAKS[kind]


def require_chips(n: int):
    """-> the first ``n`` TPU devices; raises ``NoChip`` otherwise."""
    import jax
    devices = jax.devices()
    if devices[0].platform != "tpu":
        raise NoChip(f"no TPU: JAX found {devices[0].platform} devices; "
                     f"the benchmark measures the chip and has no CPU mode")
    if len(devices) < n:
        raise NoChip(f"the cell needs {n} chips, JAX found {len(devices)}")
    return devices[:n]


def info(devices) -> dict:
    """The result line's ``device`` object. ``memory_peak_bytes`` is the
    peak on the fullest chip used."""
    d0 = devices[0]
    peak = max((d.memory_stats() or {}).get("peak_bytes_in_use", 0)
               for d in devices)
    return {"platform": d0.platform, "kind": d0.device_kind,
            "count": len(devices), "memory_peak_bytes": int(peak)}


def enable_compile_cache() -> str:
    """The program's persistent compile cache rule (``.jax_cache/`` in the
    checkout, or ``JAX_COMPILATION_CACHE_DIR``), with every program cached
    however quickly it compiled."""
    import jax
    from repro.launch.compile_cache import enable_compile_cache as enable
    path = enable()
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)
    return path
