"""A percentile of an attribute of the program's host spans: the spans
called ``name`` that began inside the window and carry a number under
``attr`` (``serving.request`` spans begin when the request was added, and
carry the engine's own ``queue_ms``, ``ttft_ms`` and ``prefill_ms``). The
percentile is numpy's, as the load generator's statistics are."""
import numpy as np


def read(rec, name, attr, q, scale=1.0):
    if "t_open" not in rec:
        return None
    vals = [s["attrs"][attr] for s in rec.get("spans", [])
            if s["name"] == name
            and rec["t_open"] <= s["t0"] <= rec["t_close"]
            and s["attrs"].get(attr) is not None]
    if not vals:
        return None
    return scale * float(np.percentile(np.asarray(vals, float), 100.0 * q))
