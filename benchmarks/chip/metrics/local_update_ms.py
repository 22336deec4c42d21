"""Device milliseconds per round on chip 0 in the scope
``fed.local_update``: the algorithm's local step and message around its
gradient call (FedCET's extrapolated update). See ``scopes.py``."""

import scopes

UNIT = "ms"


def read(ctx):
    return scopes.per_round_ms(ctx, "fed.local_update")
