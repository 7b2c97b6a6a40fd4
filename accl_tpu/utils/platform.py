"""Start-up facts every entry point shares: where compiled programs are
cached, and what the attached chip can do at most.

Platform selection itself needs no helper: jax reads ``JAX_PLATFORMS``
when it is imported, so the tests set it (plus eight forced host
devices) before their first ``import jax`` and everything that measures
simply runs with the variable unset, on the device jax finds.
"""

from __future__ import annotations

import os

#: the repo root: ``<checkout>/accl_tpu/utils/platform.py`` -> ``<checkout>``
CHECKOUT = os.path.dirname(
    os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
)


def use_compile_cache() -> str:
    """Point jax's persistent compilation cache at a fixed directory and
    return it — call before the first compile.

    Where ``JAX_COMPILATION_CACHE_DIR`` is set, jax has already read it
    and nothing is touched; otherwise the cache lives in
    ``<checkout>/.jax_cache``.  The directory is part of the cache key,
    so it is never a temporary, per-process or time-stamped path."""
    placed = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if placed:
        return placed
    import jax

    path = os.path.join(CHECKOUT, ".jax_cache")
    jax.config.update("jax_compilation_cache_dir", path)
    return path


#: Published per-chip peaks keyed by the exact ``device_kind`` jax
#: reports.  v5e: Google Cloud documentation, "TPU v5e" (197 TFLOP/s
#: bf16, 393 TOP/s int8, 819 GB/s HBM, 1,600 Gbit/s ICI); the other
#: rows carry the bf16 peak of the published spec sheets.  A kind with
#: no row is an error for anything that measures, never a default.
_V5E = {
    "bf16_flops": 197e12,
    "int8_ops": 393e12,
    "hbm_bytes_per_s": 819e9,
    "ici_bytes_per_s": 1600e9 / 8,
}
_V5P = {"bf16_flops": 459e12}
_V6E = {"bf16_flops": 918e12}
DEVICE_PEAKS = {
    "TPU v2": {"bf16_flops": 46e12},
    "TPU v3": {"bf16_flops": 123e12},
    "TPU v4": {"bf16_flops": 275e12},
    "TPU v5 lite": _V5E,
    "TPU v5e": _V5E,
    "TPU v5": _V5P,
    "TPU v5p": _V5P,
    "TPU v6 lite": _V6E,
    "TPU v6e": _V6E,
}


def device_peaks(device_kind: str) -> dict:
    """The peak row for ``device_kind``; raises ``KeyError`` naming the
    kind when the table has none."""
    try:
        return DEVICE_PEAKS[device_kind]
    except KeyError:
        raise KeyError(
            f"device_kind {device_kind!r} has no row in "
            "accl_tpu.utils.platform.DEVICE_PEAKS"
        ) from None
