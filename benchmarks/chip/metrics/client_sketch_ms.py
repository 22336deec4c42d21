"""Device milliseconds per round of the telemetry sketch kernel
(``kernels/telemetry_reduce.py:client_sketch_2d``, one call per sketched
client-state source), summed over the traced window on chip 0. In the
trace the kernel is the custom call ``%telemetry_sketch.<n>``, named after
its jitted wrapper ``kernels/ops.py:telemetry_sketch``."""

import tracefile

UNIT = "ms"


def is_kernel(name: str) -> bool:
    return name.startswith("%telemetry_sketch")


def read(ctx):
    seconds, calls = tracefile.op_seconds(ctx["trace"], 0, is_kernel)
    if not calls:
        return None
    return 1e3 * seconds / ctx["rounds"]
