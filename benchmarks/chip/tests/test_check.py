"""The output check fails a run whose timed path is broken (its round, its
batch, its exchange, its answer or its telemetry sketch), and fails the
bfloat16 control: whole runs at reduced sizes, past the harness's look for
a chip, against the cells' own limits."""

import jax
import jax.numpy as jnp
import pytest

import run
from drivers.runner import Job
from repro.core import engine
from repro.core.arena import Arena
from repro.core.fedcet import FedCET
from repro.kernels import ops
from repro.models.ssm_lm import Mamba2LM
from repro.models.transformer import TransformerLM

SEED = 2**31 + 99


def unchanged(monkeypatch):
    monkeypatch.setattr(engine.RoundEngine, "round",
                        lambda self, grad_fn, state, batches: state)


def half_batch(monkeypatch):
    for cls in (TransformerLM, Mamba2LM):
        loss = cls.loss

        def half(self, params, batch, loss=loss):
            toks = batch["tokens"]
            return loss(self, params, {"tokens": toks[: toks.shape[0] // 2]})

        monkeypatch.setattr(cls, "loss", half)


def no_exchange(monkeypatch):
    monkeypatch.setattr(engine, "tree_client_mean",
                        lambda tree, keepdims=True: tree)


def altered_answer(monkeypatch):
    aggregate = FedCET.server_aggregate

    def altered(self, *args):
        s = aggregate(self, *args)
        return s._replace(x=Arena(s.x.data.at[0, 0, 0].add(1.0),
                                  s.x.layout))

    monkeypatch.setattr(FedCET, "server_aggregate", altered)


def sketch_half_store(monkeypatch):
    sketch = ops.telemetry_sketch

    def half(data, **kw):
        return sketch(data[:, : data.shape[1] // 2], **kw)

    monkeypatch.setattr(ops, "telemetry_sketch", half)


def sketch_bin_shifted(monkeypatch):
    sketch = ops.telemetry_sketch

    def shifted(data, **kw):
        norms, hist, tv, ti = sketch(data, **kw)
        return norms, jnp.roll(hist, 1), tv, ti

    monkeypatch.setattr(ops, "telemetry_sketch", shifted)


@pytest.mark.parametrize("fault", [unchanged, half_batch, no_exchange,
                                   altered_answer, sketch_half_store,
                                   sketch_bin_shifted])
@pytest.mark.parametrize("name", ["dense_spec", "ssm_spec"])
def test_broken_timed_path_is_not_correct(name, fault, request, monkeypatch):
    spec = request.getfixturevalue(name)
    fault(monkeypatch)
    res = run.run_cell(spec, SEED, 0.2, False, jax.devices()[:1])
    assert not res["correct"], res["check"]


@pytest.mark.parametrize("name", ["dense_spec", "ssm_spec"])
def test_bfloat16_control_is_not_correct(name, request):
    spec = request.getfixturevalue(name)
    job = Job(spec["config"], spec["traffic"], SEED % run.SEED_RANGE, None)
    job.release()
    import compare

    control = compare.as_program(job.reference(jnp.bfloat16),
                                 job.sketch_spec)
    got = compare.judge(compare.numbers(control, job.reference(jnp.float32),
                                        job.sketch_spec), spec["limits"])
    assert any(n["value"] is None or n["value"] > n["limit"]
               for n in got.values()), got
