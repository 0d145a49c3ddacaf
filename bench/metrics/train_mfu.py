"""Model FLOP utilization (%) of the training window: the forward and
backward FLOPs per token that the configuration's shapes require
(``bench.flops``, no recompute) times tokens per second, over the
chip's bf16 peak."""
from bench import peaks


def reduce(view):
    if not view.get("tokens_per_s") or not view.get("flops_per_token"):
        return None
    p = peaks.load(view["device_kind"])
    return (100.0 * view["flops_per_token"] * view["tokens_per_s"]
            / p["bf16_flops_per_s"])
