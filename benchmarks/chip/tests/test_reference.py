"""Each plain reference against the program at reduced sizes: the same
weights from the seed, the same loss, and the same first segment of FedCET
rounds through the benchmark's driver."""

import jax
import numpy as np
import pytest

import compare
import run
from conftest import tiny_spec
from drivers.runner import program_config
from reference import dense, ssm
from repro.models import build_model


@pytest.mark.parametrize("config,family,seq", [("fedlm-100m", dense, 32),
                                               ("mamba2-130m", ssm, 64)])
def test_weights_and_loss(config, family, seq):
    spec = tiny_spec(config, "fedlm-100m.c4-tau2", seq_len=seq)
    cfg = spec["config"]
    model = build_model(program_config(cfg))
    key = jax.random.key(7)
    mine, theirs = family.init(cfg, key), model.init(key)
    assert (jax.tree.structure(mine) == jax.tree.structure(theirs))
    for a, b in zip(jax.tree.leaves(mine), jax.tree.leaves(theirs)):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))
    toks = jax.random.randint(jax.random.key(1), (2, seq), 0,
                              cfg["vocab_size"])
    with jax.default_matmul_precision("highest"):
        got = family.loss(mine, toks, cfg)
        want = model.loss(theirs, {"tokens": toks})
    np.testing.assert_allclose(float(got), float(want), rtol=2e-6)


@pytest.mark.parametrize("name", ["dense_spec", "ssm_spec"])
def test_first_segment_against_program(name, request):
    spec = request.getfixturevalue(name)
    res = run.run_cell(spec, 2**33 + 12345, 0.2, False, jax.devices()[:1])
    assert res["correct"], res["check"]
    for n in res["check"].values():
        assert n["value"] < 1e-4


SPEC = {"bins": 16, "lo": -8.0, "hi": 8.0, "k": 4}


def _ref(**kw):
    norms = np.asarray([[1.5, 30.0, 0.2], [2.0, 40.0, 0.3]])
    return dict({"loss": [2.0, 1.0], "grad_norm": [1.0, 1.0],
                 "dx": [1.0, 2.0, 1e-9], "d": [1.0, 1.0, 5.0],
                 "g0": [1.0, 2.0, 1e-7], "d_norm": norms,
                 "drift": norms / 10}, **kw)


def test_leaf_rule_and_nonfinite():
    ref = _ref()
    prog = compare.as_program(dict(ref, dx=[1.1, 2.0, 1.0],
                                   d=[1.0, 1.0, 0.0],
                                   loss=[2.0, float("nan")]), SPEC)
    got = compare.numbers(prog, ref, SPEC)
    # the third leaf's gradient is under 1e-3 of the median: not compared
    assert got["dx_gap"] == pytest.approx(0.1 / 1.5)
    assert got["d_gap"] == 0.0
    assert got["loss_gap"] is None
    assert got["sketch_gap"] == 0.0 and got["hist_moved"] == 0


def test_sketch_numbers():
    ref = _ref()
    # bins are one decade wide: 1.5 -> bin 8, 30 -> 9, 0.2 -> 7
    assert compare.bins_of([1.5, 30.0, 0.2, 0.0, 1e12],
                           SPEC).tolist() == [8, 9, 7, 0, 15]
    sk = compare.sketch(ref["d_norm"], SPEC)
    assert sk["top_ids"].tolist() == [[1, 0, 2], [1, 0, 2]]

    def read(edit):
        prog = compare.as_program(ref, SPEC)
        edit(prog["sketch"]["d_norm"])
        return compare.numbers(prog, ref, SPEC)

    def scaled(sk):
        sk["top_vals"][0, 0] *= 1.01

    def edge(sk):  # the program reads 0.2 just below a bin edge
        sk["top_vals"][0, 2] = 0.0999
        sk["hist"][0] = np.bincount([8, 9, 6], minlength=16)

    def moved(sk):
        sk["hist"][1] = np.roll(sk["hist"][1], 1)

    def repeated(sk):
        sk["top_ids"][0] = [1, 1, 2]

    assert read(scaled)["sketch_gap"] == pytest.approx(0.01)
    assert read(edge)["hist_moved"] == 0
    assert read(moved)["hist_moved"] == 1  # bins 7, 8, 9 -> 8, 9, 10
    assert read(repeated)["sketch_gap"] is None


def test_sketch_spec():
    assert compare.sketch_spec("memory,hist:48:-12:4,topk:4") == {
        "bins": 48, "lo": -12.0, "hi": 4.0, "k": 4}
    with pytest.raises(ValueError):
        compare.sketch_spec("memory,hist:48,topk:4")
