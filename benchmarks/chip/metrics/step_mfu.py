"""Model FLOP/s utilisation of the whole round program: the configuration's
training FLOPs per token (``flops/<family>.py``: forward and backward,
nothing recomputed, the logged loss's forward not counted) times the traced
run's client tokens per second, over the chips' bfloat16 peak."""

import importlib

UNIT = "%"


def read(ctx):
    cfg = ctx["config"]
    flops = importlib.import_module(f"flops.{cfg['family']}")
    per_token = flops.train_flops_per_token(cfg, ctx["traffic"]["seq_len"])
    peak = ctx["chips"] * ctx["peaks"]["bf16_flops_per_s"]
    return 100.0 * per_token * ctx["tokens_per_s"] / peak
