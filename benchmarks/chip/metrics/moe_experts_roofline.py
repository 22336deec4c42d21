"""Share of their roofline that the expert layer's grouped products reach:
the least time for the operations and bytes of the products the window
ran, over their device time (``moe_experts_ms``'s ops), against the
bfloat16 peak and HBM bandwidth in ``peaks.json``.

A routed slot that lands on a held expert (``moe_held_slots``, summed over
the window's rounds by ``drivers/moe.py``) passes through three products
of d x d_ff (gate, up, down) forward, three again when remat recomputes
the forward, and two for each in the backward (the input's and the
weights' gradients): 12 with remat, 9 without, of 2 d d_ff operations
each. The logged loss's forward adds three a slot on each client's last
batch of the round, taken at the round's mean held slots a step. Each
product reads its rows and writes its result, (d + d_ff) x 4 bytes a
slot, and reads the held experts' weights once a call, E d d_ff x 4
bytes."""

import moe_ops

UNIT = "%"


def products_per_slot(remat: bool) -> int:
    return 12 if remat else 9


def required(cfg: dict, traffic: dict, held_slots: int, rounds: int):
    """(operations, bytes) of the window's grouped products."""
    d, f, e = cfg["d_model"], cfg["d_ff"], cfg["n_experts"]
    n, tau, layers = traffic["n_clients"], traffic["tau"], cfg["n_layers"]
    per = products_per_slot(cfg["remat"])
    slot_products = held_slots * (per + 3 / tau)
    calls = rounds * n * layers * (per * tau + 3)
    return (slot_products * 2 * d * f,
            slot_products * (d + f) * 4 + calls * e * d * f * 4)


def read(ctx):
    held = getattr(ctx["job"], "held_slots", None)
    by = moe_ops.expert_ns(ctx)
    if not held or by is None or not by.get("moe.experts"):
        return None
    ops, nbytes = required(ctx["config"], ctx["traffic"], held,
                          ctx["rounds"])
    least = max(ops / ctx["peaks"]["bf16_flops_per_s"],
                nbytes / ctx["peaks"]["hbm_bytes_per_s"])
    return 100.0 * least / (1e-9 * by["moe.experts"])
