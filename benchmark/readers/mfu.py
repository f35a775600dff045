"""Model FLOP/s utilization (%) of a training window: the FLOPs the forward
and backward passes need per token (the ``costs`` of the configuration's
family; recomputation does not count) times tokens per second, over chips
times the chip's published peak."""
from benchmark.manifest import family_of


def read(rec):
    sc = rec.get("scalars", {})
    if ("tokens_per_s" not in sc or rec.get("kind") != "train"
            or "peak" not in rec):          # no chip, no utilization
        return None
    flops = family_of(rec["model"]).costs.train_flops_per_token(
        rec["model"], rec["plan"]["seq"])
    share = 100.0 * flops * sc["tokens_per_s"] / (
        rec["chips"] * rec["peak"].flops)
    if share > 105.0:
        raise ValueError(f"mfu {share:.1f}% > 105%")
    return share
