"""A statistic (max or mean) of one of the program's gauges over the
samples a traced run takes four times a second; ``per`` names a second
gauge to divide each sample by; ``scale`` multiplies."""


def read(rec, name, stat, per=None, scale=1.0):
    vals = []
    for t, g in rec.get("gauges", []):
        if not rec["t_open"] <= t <= rec["t_close"] or name not in g:
            continue
        v = g[name]
        if per:
            if not g.get(per):
                continue
            v = v / g[per]
        vals.append(v * scale)
    if not vals:
        return None
    return max(vals) if stat == "max" else sum(vals) / len(vals)
