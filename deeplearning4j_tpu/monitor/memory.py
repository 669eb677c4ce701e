"""HBM watermarks: runtime device-memory accounting at chunk boundaries.

The per-shard HBM budget model (``perf/epoch_cache.py``:
``total/n_shard + 2·step_bytes/(n_shard·K)``) decides whether a dataset
takes the fused path — but until now nothing ever compared that analytic
model to what the device actually holds. This module is the measurement
side:

- :func:`sample_hbm_watermark` — one point-in-time sample per local
  device: the backend's ``memory_stats()`` (``bytes_in_use`` /
  ``peak_bytes_in_use``, available on TPU) with a live-array accounting
  fallback (summing the device-local bytes of every live ``jax.Array``
  shard — exact for what THIS client allocated, blind to other clients)
  for backends like CPU that report no stats. Samples land in the
  MetricsRegistry as ``hbm_bytes_in_use`` / ``hbm_peak_bytes`` gauges
  and on the tracer as an ``hbm.watermark`` event, so the timeline
  carries the memory high-water beside the dispatch spans.
- :func:`cache_resident_bytes` — the measured per-device footprint of a
  ``DeviceDataSetCache``'s stacks (metadata walk over addressable
  shards; no transfer).
- :func:`validate_cache_budget` — the runtime check the budget model
  never had: predicted per-shard resident bytes (``cache.nbytes /
  n_shard``) vs the measured per-device maximum, with a relative
  tolerance. ``bench.py``'s epoch section embeds the verdict and
  ``tests/test_profile.py`` asserts it.

Everything here is a HOST-side readback. It is only permitted at chunk
boundaries — dl4j-lint's host-sync rule flags any of these calls
reachable from a hot path (``analysis/rules.py``
``PROFILE_READBACK_CALLS``). ``drive_epoch_chunks`` samples after each
chunk dispatch when ``DL4J_PROFILE`` is on; the default path never calls
in here.

Stdlib-only at import (jax loads lazily inside each sampler).
"""

from __future__ import annotations

import logging
from typing import Any, Dict, List, Optional

logger = logging.getLogger(__name__)

__all__ = [
    "cache_resident_bytes",
    "live_array_bytes",
    "sample_hbm_watermark",
    "validate_cache_budget",
]


def live_array_bytes() -> Dict[str, int]:
    """Per-device bytes held by live ``jax.Array``s of THIS process —
    the accounting fallback when the backend reports no memory stats.
    Metadata-only: addressable-shard sizes are host-side attributes, no
    device sync."""
    import jax

    per_device: Dict[str, int] = {}
    for arr in jax.live_arrays():
        shards = getattr(arr, "addressable_shards", None)
        if shards:
            for sh in shards:
                key = str(sh.device)
                per_device[key] = (per_device.get(key, 0)
                                   + int(sh.data.nbytes))
        else:  # pragma: no cover - non-sharded array types
            try:
                dev = str(next(iter(arr.devices())))
            except Exception:
                continue
            per_device[dev] = per_device.get(dev, 0) + int(arr.nbytes)
    return per_device


def sample_hbm_watermark(tag: Optional[str] = None,
                         record: bool = True) -> dict:
    """One watermark sample across the local devices.

    Per device: ``bytes_in_use`` and ``peak_bytes_in_use`` from the
    backend's ``memory_stats()`` when it provides them (TPU does), else
    live-array accounting (``source`` says which; the live-array walk
    runs lazily, only when some device lacks stats — a stats-capable
    backend never pays the O(live arrays) host walk per sample).
    ``record=True`` mirrors the sample into the registry gauges and
    stamps an ``hbm.watermark`` tracer event."""
    import jax

    live: Optional[Dict[str, int]] = None
    devices = []
    for d in jax.local_devices():
        stats = None
        try:
            stats = d.memory_stats()
        except Exception:  # backend without the PJRT stats API
            stats = None
        key = str(d)
        if stats:
            entry = {
                "device": key,
                "bytes_in_use": int(stats.get("bytes_in_use", 0)),
                "peak_bytes_in_use": int(
                    stats.get("peak_bytes_in_use", 0)) or None,
                "bytes_limit": int(stats.get("bytes_limit", 0)) or None,
                "source": "memory_stats",
            }
        else:
            if live is None:
                live = live_array_bytes()
            entry = {
                "device": key,
                "bytes_in_use": int(live.get(key, 0)),
                "peak_bytes_in_use": None,
                "bytes_limit": None,
                "source": "live_arrays",
            }
            entry["live_array_bytes"] = entry["bytes_in_use"]
        devices.append(entry)
    sample = {
        "tag": tag,
        "devices": devices,
        "total_bytes_in_use": sum(e["bytes_in_use"] for e in devices),
        "max_bytes_in_use": max(
            (e["bytes_in_use"] for e in devices), default=0),
    }
    if record:
        from deeplearning4j_tpu.monitor import tracer
        from deeplearning4j_tpu.monitor.registry import metrics

        reg = metrics()
        in_use = reg.gauge("hbm_bytes_in_use",
                           "per-device bytes in use at the last "
                           "watermark sample")
        peak = reg.gauge("hbm_peak_bytes",
                         "per-device peak bytes (backend-reported)")
        for e in devices:
            in_use.set(e["bytes_in_use"], device=e["device"],
                       source=e["source"])
            if e["peak_bytes_in_use"] is not None:
                peak.set(e["peak_bytes_in_use"], device=e["device"])
        tracer().event("hbm.watermark", tag=tag,
                       total_bytes=sample["total_bytes_in_use"],
                       max_device_bytes=sample["max_bytes_in_use"])
    return sample


def cache_resident_bytes(cache) -> Dict[str, int]:
    """Measured per-device bytes of a device cache's stacks. Walks the
    dataset-cache attributes (features/labels/masks; DataSet and
    MultiDataSet cache shapes both) AND the serving slot-pool attributes
    (``k``/``v``), so
    ``validate_cache_budget`` prices a ``SlotKVCache`` —
    predicted nbytes vs what the device actually holds — the same way
    it prices an epoch cache. Metadata-only, no transfer."""
    per_device: Dict[str, int] = {}
    arrays: List[Any] = []
    for attr in ("features", "labels", "features_mask", "labels_mask",
                 "features_masks", "labels_masks",
                 "k", "v"):
        val = getattr(cache, attr, None)
        if val is None:
            continue
        arrays.extend(val if isinstance(val, tuple) else [val])
    for arr in arrays:
        if arr is None:
            continue
        shards = getattr(arr, "addressable_shards", None)
        if shards:
            for sh in shards:
                key = str(sh.device)
                per_device[key] = (per_device.get(key, 0)
                                   + int(sh.data.nbytes))
        else:  # pragma: no cover - host-backed fallback caches
            per_device["host"] = (per_device.get("host", 0)
                                  + int(arr.nbytes))
    return per_device


def validate_cache_budget(cache, tolerance: float = 0.25) -> dict:
    """Check the epoch cache's analytic per-shard budget model against
    the bytes the devices actually hold.

    Predicted: ``cache.nbytes / cache.n_shard`` — the resident term of
    the PERF.md §Round-8 model (the working-set term is transient and
    not resident between chunks). Measured: the per-device maximum over
    the cache's own shards. ``within_tolerance`` is the verdict at
    relative ``tolerance`` (padding and replicated indivisible buckets
    are modeled, so the two should track closely; a drift beyond
    tolerance means the budget model no longer prices what the runtime
    allocates)."""
    predicted = cache.nbytes / max(1, cache.n_shard)
    per_device = cache_resident_bytes(cache)
    measured = max(per_device.values(), default=0)
    ratio = measured / predicted if predicted else None
    out = {
        "predicted_per_shard_bytes": int(predicted),
        "measured_per_device_bytes": int(measured),
        "n_shard": cache.n_shard,
        "n_devices_holding": len(per_device),
        "ratio": None if ratio is None else round(ratio, 4),
        "tolerance": tolerance,
        "within_tolerance": (ratio is not None
                             and abs(ratio - 1.0) <= tolerance),
    }
    if not out["within_tolerance"]:
        logger.warning(
            "epoch-cache budget model drift: predicted %d B/shard, "
            "measured %d B on the fullest device (ratio %s, tolerance "
            "%.0f%%)", out["predicted_per_shard_bytes"],
            out["measured_per_device_bytes"], out["ratio"],
            100 * tolerance)
    return out
