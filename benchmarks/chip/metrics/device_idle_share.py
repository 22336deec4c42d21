"""Share of the traced window in which no operation ran on the device, on
the least busy chip: 1 - (union of the chip's device-op intervals) / window."""

import tracefile

UNIT = "%"


def read(ctx):
    tr = ctx["trace"]
    busy = min(tracefile.busy_s(tr, i) for i in range(len(tr.devices)))
    return 100.0 * (1.0 - busy / tr.window_s)
