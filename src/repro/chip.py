"""Which device a run is on, and where it keeps its compiled programs.

``device_info`` names the device the way every result line should
(platform, device kind, count); ``require_tpu`` ends a measurement run that
found no TPU instead of letting it fall back to the CPU.
``use_compile_cache`` places JAX's persistent compilation cache: where
``JAX_COMPILATION_CACHE_DIR`` is set JAX already reads it and nothing is
changed; otherwise the cache goes to ``<root>/.jax_cache``, a fixed path (the
path is part of the cache key, so a directory that moves never hits).
Entry points call it (``chip_smoke.py``, ``benchmarks/common.time_jit``);
tests do not.
"""
from __future__ import annotations

import os
import pathlib
import sys

import jax

CACHE_ENV = "JAX_COMPILATION_CACHE_DIR"


def use_compile_cache(root) -> str:
    """Point JAX's persistent compilation cache at ``<root>/.jax_cache``
    unless ``JAX_COMPILATION_CACHE_DIR`` is set.  Returns the directory in
    use."""
    env = os.environ.get(CACHE_ENV)
    if env:
        return env
    path = str(pathlib.Path(root).resolve() / ".jax_cache")
    jax.config.update("jax_compilation_cache_dir", path)
    return path


def device_info() -> dict:
    """{"platform", "kind", "count"} of the default backend's devices."""
    devs = jax.devices()
    return {"platform": devs[0].platform, "kind": devs[0].device_kind,
            "count": len(devs)}


def require_tpu(count: int = 1) -> dict:
    """device_info(), or exit non-zero when fewer than ``count`` TPU devices
    are present — a measurement run never falls back to the CPU."""
    info = device_info()
    if info["platform"] != "tpu" or info["count"] < count:
        print(f"no TPU found: need {count} TPU device(s), JAX reports "
              f"{info['count']} x {info['platform']} ({info['kind']})",
              file=sys.stderr)
        sys.exit(2)
    return info
