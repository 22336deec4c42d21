"""Device milliseconds per round on chip 0 in the expert layer's grouped
SwiGLU products (scope ``moe.experts``, and the TPU's ``ragged-dot``
kernels that carry no scope), forward, backward and recomputed, in every
client's gradients and the logged loss's forward. See ``moe_ops.py``."""

import moe_ops

UNIT = "ms"


def read(ctx):
    return moe_ops.per_round_ms(ctx, "moe.experts")
