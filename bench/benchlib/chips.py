"""The chips a run uses, the compile cache, and what the device reports.

A measurement needs the accelerator: with no TPU, or fewer chips than
the cell asks for, the run stops with an error and prints no result. It
never falls back to the CPU."""
from __future__ import annotations

import sys
from pathlib import Path

from benchlib.spec import ROOT, load_json

PEAKS_FILE = Path(__file__).resolve().parents[1] / "peaks.json"


class NoChip(Exception):
    """JAX found no TPU, or fewer chips than the cell needs."""


def require_chips(n: int):
    import jax
    devs = jax.devices()
    if devs[0].platform != "tpu":
        raise NoChip(f"needs a TPU; JAX found platform "
                     f"{devs[0].platform!r}")
    if len(devs) < n:
        raise NoChip(f"needs {n} chips; JAX found {len(devs)}")
    return devs[:n]


def enable_compile_cache() -> str:
    """The program's own fixed cache directory inside the checkout
    (`<checkout>/.jax_cache`, or `$JAX_COMPILATION_CACHE_DIR` when set),
    caching every executable so that a second run compiles nothing."""
    import jax
    sys.path.insert(0, str(ROOT / "src"))
    from repro.launch.cache import enable_compile_cache as program_cache
    where = program_cache()
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    return where


def device_info(devs) -> dict:
    return {"platform": devs[0].platform, "kind": devs[0].device_kind,
            "count": len(devs)}


def memory_peak_bytes(devs) -> int:
    """The allocator's running peak on the fullest chip."""
    peaks = []
    for d in devs:
        stats = d.memory_stats() or {}
        peaks.append(int(stats.get("peak_bytes_in_use", 0)))
    return max(peaks)


def peaks_for(kind: str, path: Path = PEAKS_FILE) -> dict:
    table = load_json(path)
    if kind not in table["devices"]:
        raise KeyError(f"no peaks for device kind {kind!r} in {path}; "
                       f"known: {sorted(table['devices'])}")
    return table["devices"][kind]
