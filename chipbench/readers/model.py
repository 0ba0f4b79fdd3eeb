"""Per-layer metrics worked out from the configuration's shapes
(``chipbench/reduce/flops.py``) and a measured rate."""

from chipbench.reduce import flops


def mfu(view, rate):
    """Model FLOP/s utilization in percent: the counter ``rate`` (tokens/s
    per chip) times the FLOPs a token requires (no recompute), over the
    chip's published bf16 peak."""
    per_token = flops.train_flops_per_token(view["config"],
                                            int(view["traffic"]["seq_len"]))
    return flops.mfu_percent(view["counters"][rate], per_token,
                             view["peaks"]["bf16_flops_per_s"])
