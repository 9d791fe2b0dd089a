"""Persistent on-disk tuning cache (the subsystem's memory).

A single JSON file maps ``(core-spec name, dtype, M/K/N shape bucket)`` to
the tuned ``BlockConfig`` plus provenance (backend, measured/estimated
seconds, the analytical baseline it beat).  Shape dims are bucketed by
rounding up to the 128-lane MXU tile, so problem sizes that pad
identically share an entry — the paper tunes per core class, not per
matrix.

Format (``CACHE_VERSION`` guards schema drift; a version mismatch
invalidates the whole file and the caller falls back to the analytical
derivation):

.. code-block:: json

    {
      "version": 1,
      "entries": {
        "tpu-v5e/bfloat16/512x512x512": {
          "bm": 512, "bk": 512, "bn": 512,
          "dtype_bytes": 2, "acc_bytes": 4,
          "backend": "pallas",
          "measured_with": "cost-model",
          "time_s": 1.4e-3, "analytical_time_s": 1.5e-3,
          "shape": [512, 512, 512]
        }
      }
    }

``"backend"`` records the winning micro-kernel *variant* (a key of
``repro.core.execution.BACKENDS`` — e.g. ``"pallas"`` or the VMEM-lean
``"pallas_lean"``); ``"measured_with"`` records the scorer that picked it
(``"cost-model"``/``"wallclock"``).  Caches written before the variant
search stored the scorer under ``"backend"`` — consumers treat any value
outside the dispatch table as "no variant recorded", so old caches keep
working with the default kernel.

Writes are atomic and durable (``repro.util.atomic``: tempfile + fsync +
``os.replace``) so a crashed tuner never leaves a torn cache for a
training job to read.
"""

from __future__ import annotations

import dataclasses
import json
import logging
import os
from typing import Any, Optional

from repro.core.blocking import TPU_V5E, BlockConfig, TpuCoreSpec, derive_block_config
from repro.util.atomic import atomic_write_json

log = logging.getLogger(__name__)

CACHE_VERSION = 1
ENV_VAR = "REPRO_TUNING_CACHE"
ENV_SPEC_VAR = "REPRO_TUNING_SPEC"


def _bucket(dim: int) -> int:
    """Dim rounded up to the 128-lane MXU tile (min 128).

    Every feasible block is a multiple of 128, so all problem sizes in one
    bucket pad to the same dims — a tuned entry transfers exactly within
    its bucket.  (Coarser buckets, e.g. powers of two, would alias a small
    problem onto an entry whose blocks overshoot it and pay up to 8x
    padded FLOPs.)
    """

    return max(128, ((dim + 127) // 128) * 128)


def shape_bucket_key(spec_name: str, dtype_name: str, m: int, k: int, n: int) -> str:
    return f"{spec_name}/{dtype_name}/{_bucket(m)}x{_bucket(k)}x{_bucket(n)}"


@dataclasses.dataclass
class TuningCache:
    """In-memory view of one cache file; ``save()`` persists atomically."""

    path: Optional[str] = None
    entries: dict[str, dict[str, Any]] = dataclasses.field(default_factory=dict)

    # -- IO ----------------------------------------------------------------

    @classmethod
    def load(cls, path: str) -> "TuningCache":
        """Read a cache file; missing/corrupt/version-mismatched → empty."""

        if not os.path.exists(path):
            return cls(path=path)
        try:
            with open(path) as f:
                raw = json.load(f)
        except (OSError, json.JSONDecodeError) as e:
            log.warning("tuning cache %s unreadable (%s); starting empty", path, e)
            return cls(path=path)
        if not isinstance(raw, dict):
            log.warning(
                "tuning cache %s is not a JSON object (got %s); starting empty",
                path, type(raw).__name__,
            )
            return cls(path=path)
        if raw.get("version") != CACHE_VERSION:
            log.warning(
                "tuning cache %s has version %r != %d; invalidating",
                path, raw.get("version"), CACHE_VERSION,
            )
            return cls(path=path)
        return cls(path=path, entries=dict(raw.get("entries", {})))

    def save(self, path: Optional[str] = None) -> str:
        """Atomic durable write (shared ``repro.util.atomic`` helper:
        tempfile in the target dir, fsync, then ``os.replace``)."""

        path = path or self.path
        if path is None:
            raise ValueError("TuningCache.save() needs a path")
        payload = {"version": CACHE_VERSION, "entries": self.entries}
        atomic_write_json(
            path, payload, indent=1, sort_keys=True, newline=False,
            prefix=".tuning-cache-",
        )
        self.path = path
        return path

    # -- entries -----------------------------------------------------------

    def put(
        self,
        spec_name: str,
        dtype_name: str,
        m: int,
        k: int,
        n: int,
        cfg: BlockConfig,
        **meta: Any,
    ) -> str:
        key = shape_bucket_key(spec_name, dtype_name, m, k, n)
        self.entries[key] = {
            "bm": cfg.bm,
            "bk": cfg.bk,
            "bn": cfg.bn,
            "dtype_bytes": cfg.dtype_bytes,
            "acc_bytes": cfg.acc_bytes,
            "shape": [m, k, n],
            **meta,
        }
        return key

    def get(
        self, spec_name: str, dtype_name: str, m: int, k: int, n: int
    ) -> Optional[BlockConfig]:
        key = shape_bucket_key(spec_name, dtype_name, m, k, n)
        e = self.entries.get(key)
        if e is None:
            return None
        try:
            return BlockConfig(
                bm=int(e["bm"]),
                bk=int(e["bk"]),
                bn=int(e["bn"]),
                dtype_bytes=int(e.get("dtype_bytes", 2)),
                acc_bytes=int(e.get("acc_bytes", 4)),
            )
        except (KeyError, TypeError, ValueError) as err:
            # A malformed entry (hand-edited, truncated) is a miss, not a
            # crash on the kernel hot path.
            log.warning("tuning cache entry %s malformed (%s); ignoring", key, err)
            return None

    def lookup_or_analytical(
        self,
        m: int,
        k: int,
        n: int,
        *,
        spec: TpuCoreSpec = TPU_V5E,
        dtype_name: str = "bfloat16",
        dtype_bytes: int = 2,
    ) -> tuple[BlockConfig, bool]:
        """Tuned config on hit, analytical derivation on miss."""

        cfg = self.get(spec.name, dtype_name, m, k, n)
        if cfg is not None:
            log.debug("tuning cache hit %s", shape_bucket_key(spec.name, dtype_name, m, k, n))
            return cfg, True
        return derive_block_config(m, k, n, spec=spec, dtype_bytes=dtype_bytes), False


# ---------------------------------------------------------------------------
# Hot-path lookup for kernels/gemm.py: env-var gated, mtime-memoized
# ---------------------------------------------------------------------------

_memo: dict[str, tuple[float, TuningCache]] = {}


def active_cache() -> Optional[TuningCache]:
    """The cache named by ``$REPRO_TUNING_CACHE``, or None when unset.

    Reloaded only when the file's mtime changes, so the per-call cost on
    the kernel path is one ``os.stat``.
    """

    path = os.environ.get(ENV_VAR)
    if not path:
        return None
    try:
        mtime = os.stat(path).st_mtime
    except OSError:
        return None
    hit = _memo.get(path)
    if hit is not None and hit[0] == mtime:
        return hit[1]
    cache = TuningCache.load(path)
    _memo[path] = (mtime, cache)
    return cache


def cached_block_config(
    m: int,
    k: int,
    n: int,
    dtype_name: str,
    dtype_bytes: int,
    *,
    spec_name: Optional[str] = None,
) -> Optional[BlockConfig]:
    """Kernel-side lookup: tuned config or None (caller derives analytically).

    ``spec_name`` selects the per-class entry (control trees pass their
    class's core spec); when omitted, the spec the cache was tuned for is
    named by ``$REPRO_TUNING_SPEC`` (default ``tpu-v5e``).
    """

    cache = active_cache()
    if cache is None:
        return None
    if spec_name is None:
        spec_name = os.environ.get(ENV_SPEC_VAR, TPU_V5E.name)
    cfg = cache.get(spec_name, dtype_name, m, k, n)
    if cfg is not None and cfg.dtype_bytes != dtype_bytes:
        cfg = dataclasses.replace(cfg, dtype_bytes=dtype_bytes)
    return cfg


def cached_kernel_backend(
    m: int,
    k: int,
    n: int,
    dtype_name: str,
    *,
    spec_name: Optional[str] = None,
) -> Optional[str]:
    """The raw ``"backend"`` field of the active cache entry, or None.

    Returns the string as stored — callers validate it against
    ``execution.BACKENDS`` (pre-variant caches stored the measurement
    backend here; an unknown value means "no variant recorded").
    """

    cache = active_cache()
    if cache is None:
        return None
    if spec_name is None:
        spec_name = os.environ.get(ENV_SPEC_VAR, TPU_V5E.name)
    entry = cache.entries.get(shape_bucket_key(spec_name, dtype_name, m, k, n))
    if entry is None:
        return None
    backend = entry.get("backend")
    return backend if isinstance(backend, str) else None


__all__ = [
    "CACHE_VERSION",
    "ENV_VAR",
    "ENV_SPEC_VAR",
    "TuningCache",
    "shape_bucket_key",
    "active_cache",
    "cached_block_config",
    "cached_kernel_backend",
]
