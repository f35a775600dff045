"""Roofline telemetry: per-compiled-fn MFU, HBM watermarks, SLO gauges.

"How close to the hardware are we" as always-on metrics rather than a
one-off benchmark:

- **MFU** — FLOPs one call executes come from XLA's cost analysis of the
  LOWERED program (:func:`flops_of`; no second compile — ``lower()`` is
  a trace), divided by measured step time x the per-device-kind peak
  from :data:`DEVICE_SPECS` (published peaks, each with its source). Off an
  accelerator MFU is undefined: :func:`mfu` returns ``None`` on the CPU
  and the gauges stay unset. A device kind the table does not list is
  an error, never another chip's peak.
- **HBM** — ``hbm_used_bytes`` / ``hbm_peak_bytes`` gauges from PJRT
  ``memory_stats()`` (:func:`update_hbm_gauges`); silently absent where
  the backend exposes none (CPU).
- **SLO attainment** — the fraction of requests meeting
  ``FLAGS_obs_slo_ttft_ms`` / ``FLAGS_obs_slo_tpot_ms``, estimated from
  the existing TTFT/TPOT histograms by log-bucket interpolation
  (:func:`exposition.fraction_at_or_below`) — a percentile readout, not
  a raw bucket dump.

Module import stays stdlib-only (jax is imported lazily inside
functions) so the observability package keeps its no-heavy-deps
contract.
"""
from __future__ import annotations

from typing import Dict, Optional, Tuple

from ..framework.flags import define_flag, get_flag
from . import state
from .catalog import instrument as _instrument
from .exposition import _hist_state, fraction_at_or_below, \
    merged_hist_state

__all__ = ["DEVICE_SPECS", "peak_flops", "hbm_bytes", "hbm_bandwidth",
           "flops_of", "mfu", "token_count", "hbm_stats",
           "update_hbm_gauges", "slo_attainment",
           "update_serving_slo_gauges"]

define_flag("obs_slo_ttft_ms", 1000.0,
            "serving SLO target for time-to-first-token; the "
            "serving_slo_ttft_attainment gauge is the fraction of "
            "requests at or under it")
define_flag("obs_slo_tpot_ms", 250.0,
            "serving SLO target for time-per-output-token; the "
            "serving_slo_tpot_attainment gauge is the fraction of "
            "requests at or under it")

_M_HBM_USED = _instrument("hbm_used_bytes")
_M_HBM_PEAK = _instrument("hbm_peak_bytes")
_M_SLO_TTFT = _instrument("serving_slo_ttft_attainment")
_M_SLO_TPOT = _instrument("serving_slo_tpot_attainment")

# per-chip published peaks: bf16 FLOP/s, HBM bytes, HBM bytes/s — matched
# by substring against jax's device_kind; serving/training MFU and the
# benchmark share this one table. Source: Google Cloud TPU documentation,
# the "System architecture" page of each version ("TPU v4", "TPU v5p",
# "TPU v5e", "TPU v6e"): 275 / 459 / 197 / 918 TFLOP/s bf16, 32 / 95 / 16 /
# 32 GB of HBM at 1,200 / 2,765 / 819 / 1,640 GB/s.
DEVICE_SPECS: Dict[str, Tuple[float, float, float]] = {
    #             flops    hbm    hbm B/s
    "v4":        (275e12, 32e9, 1.20e12),
    "v5p":       (459e12, 95e9, 2.77e12),
    "v5e":       (197e12, 16e9, 8.19e11),
    "v5 lite":   (197e12, 16e9, 8.19e11),     # what a v5e chip reports
    "v6e":       (918e12, 32e9, 1.64e12),
    "v6 lite":   (918e12, 32e9, 1.64e12),     # what a v6e chip reports
    "trillium":  (918e12, 32e9, 1.64e12),
}


def _device(device=None):
    if device is not None:
        return device
    import jax

    return jax.devices()[0]


def _spec(device, idx: int) -> float:
    kind = (getattr(_device(device), "device_kind", "") or "").lower()
    for key, vals in DEVICE_SPECS.items():
        if key in kind:
            return vals[idx]
    raise ValueError(
        f"device kind {kind!r} is not in observability.perf.DEVICE_SPECS: "
        "add its published peaks and their source — a roofline against "
        "another chip's peak is a wrong number, not an estimate")


def peak_flops(device=None) -> float:
    """bf16 peak FLOP/s of ``device`` (default: device 0); raises
    ``ValueError`` for a device kind the table does not list (the CPU
    included — see :func:`mfu`)."""
    return _spec(device, 0)


def hbm_bytes(device=None) -> float:
    return _spec(device, 1)


def hbm_bandwidth(device=None) -> float:
    return _spec(device, 2)


def flops_of(fn, *args, **kwargs) -> Optional[float]:
    """FLOPs one ``fn(*args)`` call executes, from XLA cost analysis of
    the lowered program. ``fn`` may be a plain jittable or an existing
    ``jax.jit`` object (its AOT ``lower`` is reused — donation marks and
    static partials survive). Lowering is a trace, never a compile; the
    caller should cache the result per executable (the train loop caches
    per run, the serving engine per decode variant). Returns ``None``
    when the fn doesn't trace or the backend offers no analysis."""
    try:
        import jax

        jitted = fn if hasattr(fn, "lower") else jax.jit(fn)
        ca = jitted.lower(*args, **kwargs).cost_analysis()
        f = float(ca.get("flops", -1.0)) if ca else -1.0
        return f if f > 0 else None
    except Exception:
        return None


def mfu(flops_per_step: Optional[float], step_seconds: float,
        device=None) -> Optional[float]:
    """Model FLOP utilization: cost-model FLOPs / (wall x peak).
    ``None`` on the CPU, where no peak is defined."""
    if not flops_per_step or not step_seconds or step_seconds <= 0:
        return None
    dev = _device(device)
    if getattr(dev, "platform", None) == "cpu":
        return None
    return float(flops_per_step) / (float(step_seconds) * peak_flops(dev))


def token_count(batch) -> int:
    """Token count of one batch: total elements of its integer-dtype
    array leaves (token-id tensors); 0 when it has none (the tokens/s
    gauge then stays unset)."""
    try:
        import numpy as np
        from jax import tree_util

        leaves = tree_util.tree_leaves(batch)
        total = 0
        for leaf in leaves:
            dt = getattr(leaf, "dtype", None)
            shape = getattr(leaf, "shape", None)
            if dt is None or shape is None:
                continue
            if np.issubdtype(np.dtype(dt), np.integer):
                total += int(np.prod(shape)) if shape else 1
        return total
    except Exception:
        return 0


def hbm_stats(device_id: int = 0) -> Dict[str, int]:
    """``{bytes_in_use, peak_bytes_in_use}`` of one device via PJRT;
    ``{}`` where the backend exposes no stats (CPU)."""
    try:
        from ..device import _memory

        s = _memory._stats(device_id=device_id)
    except Exception:
        return {}
    if not s:
        return {}
    used = int(s.get("bytes_in_use", 0))
    return {"bytes_in_use": used,
            "peak_bytes_in_use": int(s.get("peak_bytes_in_use", used))}


def update_hbm_gauges(device_id: int = 0) -> Dict[str, int]:
    """Refresh the HBM gauges from device ``device_id``; returns the raw
    stats dict (empty where unavailable). No-op while disabled."""
    if not state.enabled():
        return {}
    s = hbm_stats(device_id)
    if s:
        _M_HBM_USED.set(s["bytes_in_use"])
        _M_HBM_PEAK.set(s["peak_bytes_in_use"])
    return s


def slo_attainment(hist, threshold_seconds: float) -> Optional[float]:
    """Fraction of a histogram's observations at or under the target
    (log-bucket interpolated); ``None`` while it is empty. ``hist`` is a
    Histogram family (read family-wide, merged across children — under
    r17 replica scoping the observations live in ``{replica=...}``
    series) or a single child (the per-replica burn-rate path)."""
    if callable(getattr(hist, "series", None)):
        counts, _sum, count = merged_hist_state(hist)
    else:
        counts, _sum, count = _hist_state(hist)
    if not count:
        return None
    return fraction_at_or_below(hist.bounds, counts, threshold_seconds)


def update_serving_slo_gauges(ttft_hist, tpot_hist) -> None:
    """Refresh both SLO-attainment gauges from the live TTFT/TPOT
    histograms against the FLAGS_obs_slo_* targets. The gauges are
    process-global (fleet-wide under a router), so they write through
    the labelless child directly — bypassing any replica scope on the
    calling step thread, which would mislabel the fleet-wide value as
    one replica's."""
    a = slo_attainment(ttft_hist, float(get_flag("obs_slo_ttft_ms")) / 1e3)
    if a is not None:
        _M_SLO_TTFT.labels().set(a)
    a = slo_attainment(tpot_hist, float(get_flag("obs_slo_tpot_ms")) / 1e3)
    if a is not None:
        _M_SLO_TPOT.labels().set(a)
