"""Share of chip 0's leaf-op time in the window that none of the program's
layer scopes covers: loop plumbing, layout copies and ops with no scope.
See ``scopes.py``."""

import scopes

UNIT = "%"


def read(ctx):
    return scopes.unscoped_share(ctx)
