"""The sparse-expert family (``reference/moe.py``, ``drivers/moe.py``,
``flops/moe.py``) against the program at reduced sizes: the same weights
from the seed, the same loss, the same first segment of FedCET rounds
through the benchmark's driver, and the FLOP count against a hand count."""

import jax
import numpy as np
import pytest

import run
from drivers.runner import program_config
from flops import moe as moe_flops
from reference import moe
from repro.models import build_model

CELL = "granite-moe-3b-a800m.c2-tau8"
#: the configuration cut to CPU size, its share and multipliers kept:
#: experts 2..5 of 8 held, 2 a token
TINY = dict(n_layers=2, d_model=64, n_heads=4, n_kv_heads=2, head_dim=16,
            d_ff=32, vocab_size=256, n_experts=4, router_experts=8,
            expert_offset=2, experts_per_token=2)


@pytest.fixture
def moe_spec():
    cfg = run.load_json("configs", "granite-moe-3b-a800m.json")
    cfg.update(TINY)
    traffic = run.load_json("traffic", "c2-tau8.json")
    traffic.update(n_clients=2, tau=2, batch=2, seq_len=32, rounds_per_call=2)
    return {"cell": {"chips": 1}, "config": cfg, "traffic": traffic,
            "limits": run.load_json("limits", CELL + ".json"),
            "per_layer": []}


def test_weights_and_loss(moe_spec):
    cfg = moe_spec["config"]
    model = build_model(program_config(cfg))
    key = jax.random.key(7)
    mine, theirs = moe.init(cfg, key), model.init(key)
    assert jax.tree.structure(mine) == jax.tree.structure(theirs)
    for a, b in zip(jax.tree.leaves(mine), jax.tree.leaves(theirs)):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))
    toks = jax.random.randint(jax.random.key(1), (2, 32), 0,
                              cfg["vocab_size"])
    with jax.default_matmul_precision("highest"):
        got = moe.loss(mine, toks, cfg)
        want = model.loss(theirs, {"tokens": toks})
    np.testing.assert_allclose(float(got), float(want), rtol=2e-6)


def test_first_segment_against_program(moe_spec):
    res = run.run_cell(moe_spec, 2**33 + 12345, 0.2, False, jax.devices()[:1])
    assert res["correct"], res["check"]
    for n in res["check"].values():
        assert n["value"] < 1e-4


def test_moe_flops_by_hand():
    cfg = dict(n_layers=2, d_model=8, n_heads=2, n_kv_heads=1, head_dim=4,
               d_ff=16, vocab_size=32, n_experts=2, router_experts=4,
               experts_per_token=2)
    s = 4
    # per token and layer: q 8x8, k 8x4, v 8x4, o 8x8, router 8x4, and one
    # held slot a token (2 of 4 experts held, 2 chosen) of 3 x 8x16
    mm = 64 + 32 + 32 + 64 + 32 + 1 * 3 * 128
    attn = 2 * s * 8                       # q.k and p.v over 4 keys
    fwd_seq = 2 * s * (2 * mm + 2 * attn) + (s - 1) * 2 * 8 * 32
    assert moe_flops.train_flops_per_token(cfg, s) == pytest.approx(
        3 * fwd_seq / s)
