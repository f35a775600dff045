"""Share (%) of the traced window that a chip's core spent in collective
operations (all-gather, all-reduce, reduce-scatter, all-to-all,
collective-permute and their -start/-done halves on the ``XLA Ops`` line):
operations run one at a time there, so while one of these runs no compute
does. Averaged over the chips."""


def read(rec):
    red = rec.get("trace")
    if not red or not red["window_s"] or red["chips"] < 2:
        return None
    return 100.0 * red["collective_s"] / red["window_s"]
