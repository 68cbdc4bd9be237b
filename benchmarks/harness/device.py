"""The chip: what is there, what it can do at most, how full it got."""

from __future__ import annotations

# Published peaks by `device_kind` as JAX reports it. Source: Google
# Cloud documentation, "TPU v5e" system architecture page: 197 TFLOP/s
# bf16, 16 GB HBM2e at 819 GB/s per chip. A device that is not in the
# table is an error, not a default.
PEAKS = {
    "TPU v5 lite": {"flops_bf16": 197e12, "hbm_bytes_per_s": 819e9,
                    "hbm_bytes": 16e9},
    "TPU v5e": {"flops_bf16": 197e12, "hbm_bytes_per_s": 819e9,
                "hbm_bytes": 16e9},
}


class NoChip(SystemExit):
    pass


def peaks_of(kind: str) -> dict:
    if kind not in PEAKS:
        raise KeyError(f"no published peaks for device kind {kind!r}; add "
                       f"them to benchmarks/harness/device.py with a source")
    return PEAKS[kind]


def require_chips(chips: int, rehearsal: bool = False) -> dict:
    """The device as JAX reports it. Fails unless it is a TPU with at
    least `chips` chips; `rehearsal` (tests only, never a flag of the
    command) lets the CPU through."""
    import jax

    devs = jax.devices()
    d = devs[0]
    if d.platform != "tpu" and not rehearsal:
        raise NoChip(f"benchmark: no accelerator: jax.devices()[0].platform "
                     f"is {d.platform!r}, not 'tpu'")
    if len(devs) < chips:
        raise NoChip(f"benchmark: the cell needs {chips} chips, jax reports "
                     f"{len(devs)}")
    return {"platform": d.platform, "kind": d.device_kind, "count": len(devs)}


def memory_peak_bytes() -> int:
    """Peak bytes in use on the fullest device so far in this process."""
    import jax

    peaks = [(d.memory_stats() or {}).get("peak_bytes_in_use", 0)
             for d in jax.devices()]
    return int(max(peaks))
