"""Device milliseconds per round on chip 0 in the expert layer's routing
(scope ``moe.route``: router, top-k, the sort of the routed slots, the
gather of their rows and the weighted combine), in every client's
gradients and the logged loss's forward. See ``moe_ops.py``."""

import moe_ops

UNIT = "ms"


def read(ctx):
    return moe_ops.per_round_ms(ctx, "moe.route")
