"""The chips a run uses, and what it reports about them."""
from __future__ import annotations


class NoChip(RuntimeError):
    """JAX found no TPU, or fewer chips than the cell asks for."""


def require(chips: int, log):
    """JAX's devices, after checking there are ``chips`` TPUs; there is
    no fallback to the CPU."""
    import jax
    devs = jax.devices()
    d = devs[0]
    log(f"devices: {devs}")
    log(f"platform={d.platform} kind={d.device_kind} count={len(devs)}")
    if d.platform != "tpu":
        raise NoChip(f"JAX's device is {d.platform!r}, not a TPU; the "
                     f"benchmark measures only on the chip")
    if len(devs) < chips:
        raise NoChip(f"the cell needs {chips} chips, JAX sees {len(devs)}")
    return devs


def describe(devs) -> dict:
    d = devs[0]
    return {"platform": d.platform, "kind": d.device_kind,
            "count": len(devs)}


def memory_peak(devs) -> int:
    """Peak bytes in use on the fullest of ``devs``."""
    peak = 0
    for d in devs:
        st = d.memory_stats() or {}
        peak = max(peak, int(st.get("peak_bytes_in_use", 0)))
    return peak
