"""Model operations per token (6N plus causal attention, recomputation not
counted; ``chipbench/costs.py``) times tokens trained per second, over the
chip's peak bf16 rate."""
from chipbench import costs


def read(run):
    n = run.stats.get("window_tokens")
    if not n:
        return None
    per_tok = costs.train_flops_per_token(run.config["arch"],
                                          run.traffic["seq"])
    return 100.0 * per_tok * n / run.window_s / run.peak["bf16_flops_per_s"]
