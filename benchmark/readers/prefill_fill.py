"""Real prompt tokens over rows x bucket of the prefill waves of the
window (%), from the program's ``serving.prefill`` host spans (each names
its bucket, its padded batch and the requests in it) and the prompt lengths
the load generator sent those requests with."""
from benchmark.readers_util import prefill_waves


def read(rec):
    if "t_open" not in rec:
        return None
    waves = prefill_waves(rec, rec["t_open"], rec["t_close"])
    padded = sum(w["batch"] * w["bucket"] for w in waves)
    real = sum(sum(w["rows"]) for w in waves)
    return 100.0 * real / padded if padded else None
