"""Seconds the program spent inside some of its host spans over the window,
per occurrence of another span (milliseconds by default).

``spans`` names the spans whose durations are summed, among those that
began inside the window; ``per`` names the span whose occurrences divide
the sum (``serving.step``: per engine step). Without ``per`` the sum is
divided by the number of the summed spans themselves: their mean. A
program that records no such span reads nothing."""


def read(rec, spans, per=None, scale=1000.0):
    if "t_open" not in rec:
        return None
    inside = [s for s in rec.get("spans", [])
              if rec["t_open"] <= s["t0"] <= rec["t_close"]]
    mine = [s["t1"] - s["t0"] for s in inside if s["name"] in spans]
    if not mine:
        return None
    count = (sum(1 for s in inside if s["name"] == per) if per
             else len(mine))
    return scale * sum(mine) / count if count else None
