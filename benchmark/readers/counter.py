"""The change of one of the program's counters over the window, read from
its registry's snapshots at the window's opening and close.

``name``/``labels`` pick the series (labels as a subset; no labels sums the
labelled children); ``part`` is ``value`` for a counter, ``sum`` or
``count`` for a histogram. ``per`` is a second such pick to divide by
(``per_attempted`` divides by the requests attempted); ``scale``
multiplies."""


def _pick(snap, name, labels=None, part="value"):
    for m in snap["metrics"]:
        if m["name"] == name:
            vals = [s[part] for s in m["series"]
                    if all(s["labels"].get(k) == v
                           for k, v in (labels or {}).items())]
            return sum(vals) if vals else None
    return None


def delta(rec, name, labels=None, part="value"):
    if "snap_open" not in rec:
        return None
    a = _pick(rec["snap_open"], name, labels, part)
    b = _pick(rec["snap_close"], name, labels, part)
    if b is None:
        return None
    return b - (a or 0.0)


def read(rec, name, labels=None, part="value", per=None,
         per_attempted=False, scale=1.0):
    num = delta(rec, name, labels, part)
    if num is None:
        return None
    if per_attempted:
        den = rec.get("attempted")
    elif per:
        den = delta(rec, **per)
    else:
        return num * scale
    return None if not den else num * scale / den
