"""Device milliseconds per round on chip 0 in the scope ``fed.round_loss``:
the logged per-round loss (the runner's ``metric_fn``). See ``scopes.py``."""

import scopes

UNIT = "ms"


def read(ctx):
    return scopes.per_round_ms(ctx, "fed.round_loss")
