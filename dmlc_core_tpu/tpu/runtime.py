"""Process-level JAX runtime setup shared by every entry point that jits:
where the persistent compile cache lives, what the compiler did, and
which device the process ran on.

One process drives all chips of a host; a second process that opens the
chip fails or hangs. So entry points call :func:`enable_compile_cache`
once, before their first compilation, and print :func:`device_banner`
once, so every log names the platform it measured.
"""

from __future__ import annotations

import os
from typing import Any, Dict

import jax

from dmlc_core_tpu import telemetry

__all__ = ["enable_compile_cache", "install_compile_monitor",
           "compile_report", "device_report", "device_banner"]

_CHECKOUT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))


def enable_compile_cache() -> str:
    """Turn on JAX's persistent compilation cache and return its
    directory. With ``JAX_COMPILATION_CACHE_DIR`` set, jax has already
    read it and nothing is set in code; otherwise the cache lives at the
    fixed ``<checkout>/.jax_cache`` (the path is part of the cache key:
    a directory that moves never hits), and programs that compile in
    under jax's default one-second floor are cached too, since a cold
    chip start is mostly many small programs. Call before the first
    compilation; also installs the compile monitor.

    The operations' metadata is part of the cache key. By jax's default
    it is not, and an executable loaded from the cache then carries the
    ``op_name``s and source lines of whichever program compiled it first:
    a profile of a step whose ``jax.named_scope``s are newer than the
    cache entry shows none of them (seen on the chip, PERF.md section 6,
    PR 26). A change of scopes or line numbers compiles once more; a
    profile never lies."""
    install_compile_monitor()
    jax.config.update("jax_compilation_cache_include_metadata_in_key", True)
    if os.environ.get("JAX_COMPILATION_CACHE_DIR"):
        return jax.config.jax_compilation_cache_dir
    path = os.path.join(_CHECKOUT, ".jax_cache")
    jax.config.update("jax_compilation_cache_dir", path)
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    return path


_monitor_installed = False


def install_compile_monitor() -> None:
    """Route jax.monitoring's compilation events into the telemetry
    plane, once per process: every trace/lower/compile phase duration
    lands in ``device_compile_us``; ``device_jit_compiles_total`` counts
    trips through the backend-compile stage and
    ``device_compile_cache_hits_total`` the ones the persistent cache
    answered, so their difference is what the compiler actually built."""
    global _monitor_installed
    if _monitor_installed:
        return
    _monitor_installed = True
    from jax import monitoring
    compiles = telemetry.counter("device_jit_compiles_total")
    hits = telemetry.counter("device_compile_cache_hits_total")
    compile_us = telemetry.histogram("device_compile_us")

    def on_duration(event, duration, **_kw):
        if event.startswith("/jax/core/compile/"):
            compile_us.observe(duration * 1e6)
            if "backend_compile" in event:
                compiles.inc()

    def on_event(event, **_kw):
        if event == "/jax/compilation_cache/cache_hits":
            hits.inc()

    monitoring.register_event_duration_secs_listener(on_duration)
    monitoring.register_event_listener(on_event)


def compile_report() -> Dict[str, Any]:
    """What compilation cost this process so far: the cache directory,
    programs the backend built (cache misses), programs the persistent
    cache answered, and seconds spent across every compile phase."""
    trips = telemetry.counter("device_jit_compiles_total").value
    hits = telemetry.counter("device_compile_cache_hits_total").value
    return {"cache_dir": jax.config.jax_compilation_cache_dir,
            "backend_compiles": int(trips - hits),
            "cache_hits": int(hits),
            "compile_seconds": round(
                telemetry.histogram("device_compile_us").sum / 1e6, 3)}


def device_report(mesh=None) -> Dict[str, Any]:
    """The device this process runs on, as jax reports it, plus the
    mesh axes when a mesh is given."""
    devs = jax.devices()
    report: Dict[str, Any] = {"platform": devs[0].platform,
                              "device_kind": devs[0].device_kind,
                              "device_count": len(devs)}
    if mesh is not None:
        report["mesh"] = {str(k): int(v) for k, v in mesh.shape.items()}
    return report


def device_banner(r: Dict[str, Any]) -> str:
    """One printable line from a :func:`device_report`."""
    mesh = ",".join(f"{k}={v}" for k, v in r.get("mesh", {}).items())
    return (f"device: platform={r['platform']} "
            f"device_kind={r['device_kind']!r} count={r['device_count']}"
            + (f" mesh={mesh}" if mesh else ""))
