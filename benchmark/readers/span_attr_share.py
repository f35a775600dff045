"""The share (%) of one of the program's host spans, among those that began
inside the window, whose attribute ``attr`` is true. Spans that do not
carry the attribute at all are left out of both counts, so a program
without it reads nothing."""


def read(rec, span, attr):
    if "t_open" not in rec:
        return None
    have = [s["attrs"][attr] for s in rec.get("spans", [])
            if s["name"] == span and attr in s["attrs"]
            and rec["t_open"] <= s["t0"] <= rec["t_close"]]
    return 100.0 * sum(bool(v) for v in have) / len(have) if have else None
