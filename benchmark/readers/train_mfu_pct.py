"""Model FLOP/s utilization of training: tokens per second of the window
times the operations a token needs forward and backward
(``costs.train_flops_per_token``), over chips times the published peak."""

from benchmark import costs


def read(ctx):
    if ctx["rehearse"]:
        return None
    peak = costs.peaks(ctx["device"]["kind"])["flops_bf16"]
    per_token = costs.train_flops_per_token(ctx["config"], ctx["traffic"]["seq_len"])
    rate = ctx["end_to_end"]["train_tokens_per_s"]
    return 100.0 * rate * per_token / (ctx["device"]["count"] * peak)
