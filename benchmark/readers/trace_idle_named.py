"""Share (%) of the traced stretch's idle time that the trace puts down to
one of ``names``, by ``benchmark/trace.py``'s ``idle_gaps`` (each idle
stretch goes to the innermost host span over it).

The idle time is the window less the busy time, less the seams between
operations (stretches under 20 us, ``(between operations)``), which no
host span explains. With ``names`` = the whole step's span and ``(no host
span)`` this is the idle time that no phase of the step accounts for."""

SEAMS = "(between operations)"


def read(rec, names):
    red = rec.get("trace")
    if not red or not red["window_s"]:
        return None
    gaps = dict((name, sec) for name, sec in red["idle_gaps"])
    idle = red["window_s"] - red["busy_s"] - gaps.get(SEAMS, 0.0)
    if idle <= 0.0:
        return None
    return 100.0 * sum(gaps.get(n, 0.0) for n in names) / idle
