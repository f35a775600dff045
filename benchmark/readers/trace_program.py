"""Device time of the runs of a compiled program in the traced stretch.
``pattern`` matches the program's name on the ``XLA Modules`` line;
``contains``/``lacks`` match the text of the operations inside a run (the
engine's programs share one name today, see ``benchmark/trace.py``).
``per="run"`` gives milliseconds a run; ``per="ktok"`` milliseconds per
thousand real prompt tokens, the tokens taken from the ``serving.prefill``
spans inside the traced stretch."""
from benchmark import trace
from benchmark.readers_util import traced_prefill_rows


def read(rec, pattern, contains=None, lacks=None, per="run"):
    red = rec.get("trace")
    if not red:
        return None
    runs = trace.module_runs(red, pattern, contains, lacks)
    if not runs:
        return None
    total_ms = sum(d for _s, d in runs) / 1e6
    if per == "run":
        return total_ms / len(runs)
    toks = sum(sum(r) for r in traced_prefill_rows(rec))
    return total_ms / (toks / 1000.0) if toks else None
