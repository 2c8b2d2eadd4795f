"""Model FLOP utilization of the whole step over the traced window, in %:
model FLOPs per token (the architecture's ``train_flops_per_token``) times
the tokens completed per second, over the chips' bf16 peak."""


def read(run):
    if run.steps == 0 or run.window_s <= 0:
        return None
    tokens_per_s = run.steps * run.tokens_per_step / run.window_s
    per_token = run.arch.train_flops_per_token(run.config, run.traffic["seq_len"])
    return 100.0 * per_token * tokens_per_s / (run.chips * run.peak["bf16_flops_per_s"])
