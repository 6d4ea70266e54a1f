"""Model FLOP/s of the feed over the chip's peak, in percent: model FLOPs
per token (``bench/flops.py``: forward and backward, no recomputation,
causal attention counted at half) times tokens per second, over the bf16
peak of the chips used."""
from bench import flops


def read(run):
    tokens = run.counters.get("tokens")
    if not tokens:
        return None
    per_token = flops.lm_train_flops_per_token(run.config,
                                               run.traffic["seq_len"])
    peak = run.peaks["bf16_flops_per_s"] * run.chips
    return 100.0 * per_token * tokens / run.window_s / peak
