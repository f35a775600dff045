"""A statistic of a series the load generator recorded: ``field`` is one of
``benchmark/clientstats.py``'s series (ttft_ms, itl_ms, late_ms), ``stat``
is mean, max or p<percentile>."""
from benchmark import clientstats


def read(rec, field, stat):
    if "client" not in rec:
        return None
    return clientstats.stat(clientstats.series(rec["client"], field), stat)
