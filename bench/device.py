"""The device: the check that a TPU is there, the table of peaks, JAX's
compile cache in the checkout, and counters of compiles.

Copied pieces: ``PEAKS`` from ``benchmarks/roofline.py`` and the
``jax.monitoring`` compile and cache-hit listeners of ``chip_smoke.py``.
"""

from __future__ import annotations

from pathlib import Path

# Published per-chip peaks, keyed by jax's ``device_kind``.  Source: Google
# Cloud documentation, "TPU v5e": 197 TFLOP/s bf16, 16 GB HBM at 819 GB/s.
PEAKS = {
    "TPU v5 lite": {"flops": 197e12, "hbm_bw": 819e9},
}

CACHE_DIR = Path(__file__).resolve().parent / ".jax_cache"


class NoAccelerator(RuntimeError):
    pass


def peaks(device_kind: str) -> dict:
    """The peak table's row for ``device_kind``; raises for any other."""
    try:
        return PEAKS[device_kind]
    except KeyError:
        raise ValueError(
            f"no published peaks for device kind {device_kind!r}; known: "
            f"{sorted(PEAKS)}") from None


def enable_cache() -> str:
    """Keep every compile in ``bench/.jax_cache`` (a fixed path in the
    checkout, so a later run of the same checkout finds it)."""
    import jax
    CACHE_DIR.mkdir(exist_ok=True)
    jax.config.update("jax_compilation_cache_dir", str(CACHE_DIR))
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)
    return str(CACHE_DIR)


def check(chips: int, platform: str = "tpu") -> dict:
    """The device record of the result line; raises NoAccelerator when JAX
    finds no ``platform`` device or fewer than ``chips`` of them."""
    import jax
    devs = jax.devices()
    if devs[0].platform != platform or len(devs) < chips:
        raise NoAccelerator(
            f"the cell needs {chips} {platform} device(s); JAX found "
            f"{len(devs)} {devs[0].platform} device(s)")
    return {"platform": devs[0].platform, "kind": devs[0].device_kind,
            "count": chips}


def memory_peak_bytes(chips: int) -> int:
    import jax
    return max(int((d.memory_stats() or {}).get("peak_bytes_in_use", 0))
               for d in jax.devices()[:chips])


class CompileCounter:
    """Backend compiles and persistent-cache hits, from JAX's monitoring
    events; ``compiles`` counts cache loads too (the backend event fires
    for both)."""

    def __init__(self):
        import jax
        self.compiles = 0
        self.compile_s = 0.0
        self.cache_hits = 0
        self.names: list = []

        def on_duration(event, secs, fun_name="?", **_):
            if event == "/jax/core/compile/backend_compile_duration":
                self.compiles += 1
                self.compile_s += secs
                self.names.append(fun_name)

        def on_event(event, **_):
            if event == "/jax/compilation_cache/cache_hits":
                self.cache_hits += 1

        jax.monitoring.register_event_duration_secs_listener(on_duration)
        jax.monitoring.register_event_listener(on_event)
