"""A quantile of one of the program's histograms over the window: the
bucket counts at the close minus those at the opening, interpolated inside
the bucket on a log scale (the buckets are logarithmic)."""
import math


def _series(snap, name):
    for m in snap["metrics"]:
        if m["name"] == name and m["kind"] == "histogram":
            for s in m["series"]:
                if not s["labels"]:
                    return s
    return None


def read(rec, name, q, scale=1.0):
    if "snap_open" not in rec:
        return None
    b = _series(rec["snap_close"], name)
    if b is None:
        return None
    a = _series(rec["snap_open"], name)
    counts = [y - (a["counts"][i] if a else 0)
              for i, y in enumerate(b["counts"])]
    total = sum(counts)
    if total <= 0:
        return None
    bounds = b["bounds"]
    want, seen = q * total, 0.0
    for i, c in enumerate(counts):
        if c and seen + c >= want:
            hi = bounds[i] if i < len(bounds) else bounds[-1]
            lo = bounds[i - 1] if i > 0 else hi / (bounds[1] / bounds[0])
            if i >= len(bounds):
                return hi * scale
            f = (want - seen) / c
            return math.exp(math.log(lo) + f * (math.log(hi) - math.log(lo))
                            ) * scale
        seen += c
    return bounds[-1] * scale
