"""CPU tests of the chip benchmark. Run them by path, from the checkout
root: ``python -m pytest benchmarks/chip/tests``."""

import os
import sys

os.environ.setdefault("JAX_PLATFORMS", "cpu")
HERE = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path[:0] = [HERE, os.path.join(os.path.dirname(os.path.dirname(HERE)),
                                   "src")]

import pytest  # noqa: E402

import run  # noqa: E402

#: reduced sizes of the two configurations, small enough for the CPU
TINY = {
    "fedlm-100m": dict(n_layers=2, d_model=64, n_heads=4, n_kv_heads=2,
                       head_dim=16, d_ff=128, vocab_size=256),
    "mamba2-130m": dict(n_layers=2, d_model=64, ssm_state=16,
                        ssm_headdim=16, vocab_size=256),
}


def tiny_spec(config: str, cell: str, seq_len: int = 32) -> dict:
    """A cell's spec with its configuration cut to TINY and its traffic to
    2 clients, tau 2, batch 2 x ``seq_len``, 2 rounds a call; the cell's
    own limits."""
    spec_cfg = run.load_json("configs", config + ".json")
    spec_cfg.update(TINY[config])
    traffic = run.load_json("traffic", cell.split(".", 1)[1] + ".json")
    traffic.update(n_clients=2, tau=2, batch=2, seq_len=seq_len,
                   rounds_per_call=2)
    return {"cell": {"chips": 1}, "config": spec_cfg, "traffic": traffic,
            "limits": run.load_json("limits", cell + ".json"),
            "per_layer": []}


@pytest.fixture
def dense_spec():
    return tiny_spec("fedlm-100m", "fedlm-100m.c4-tau2")


@pytest.fixture
def ssm_spec():
    return tiny_spec("mamba2-130m", "mamba2-130m.c2-tau16", seq_len=64)
