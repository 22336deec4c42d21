"""Share of its roofline that the telemetry sketch kernel reaches: the least
time for the bytes a sketch must move, over the kernel's device time. A
sketch reads each client's state vector once (clients x parameters x 4
bytes of float32) and writes a norm per client and one histogram, which is
negligible; it does a few operations per element, so bytes bound it."""

import tracefile

from metrics.client_sketch_ms import is_kernel

UNIT = "%"


def required_bytes(n_clients: int, n_params: int, itemsize: int = 4) -> int:
    return n_clients * n_params * itemsize


def read(ctx):
    seconds, calls = tracefile.op_seconds(ctx["trace"], 0, is_kernel)
    if not calls:
        return None
    need = calls * required_bytes(ctx["traffic"]["n_clients"],
                                  ctx["job"].n_params)
    return 100.0 * need / ctx["peaks"]["hbm_bytes_per_s"] / seconds
