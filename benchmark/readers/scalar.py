"""A number the cell worked out itself (``rec["scalars"][key]``): set-up
seconds, tokens per second of the window, memory peaks, the step's median."""


def read(rec, key, scale=1.0):
    v = rec.get("scalars", {}).get(key)
    return None if v is None else v * scale
