"""Layer scopes of the round program and host spans of the training loop.

The engine names the round program's layers with ``telemetry.scope``
(``telemetry.SCOPES``); a profiler trace's device ops are attributed to a
layer through the compiled program's ``op_name`` metadata. These tests pin
that every scope reaches that metadata, that the scopes change nothing but
metadata, and that ``run_training`` under ``--trace-rounds`` records its
host spans, ``drain``'s included.
"""

import contextlib
import functools
import glob
import os
import re
from unittest import mock

import jax
import jax.numpy as jnp
import pytest

from repro.configs import get_config
from repro.configs.base import FedScenario
from repro.core import telemetry as tele
from repro.core.engine import make_round_runner
from repro.core.fedcet import FedCET
from repro.models import build_model

TELEMETRY = "hist:48:-12:4,topk:4"
N_CLIENTS, TAU, BATCH, SEQ, ROUNDS = 2, 2, 2, 16, 2

#: an instruction's metadata (quoted strings may hold braces), the
#: stack-frame tables its ids point into, and an instruction's name
_METADATA = re.compile(r', metadata=\{(?:[^{}"]|"(?:[^"\\]|\\.)*")*\}')
_FRAME_TABLES = re.compile(
    r"^(?:FileNames|FunctionNames|FileLocations|StackFrames)\n(?:.+\n)*",
    re.M)
_NAME = re.compile(r"%([\w.\-]+)")


def _null_scope(name):
    del name
    return contextlib.nullcontext()


@functools.lru_cache(maxsize=None)
def _compiled_text(telemetry: bool, scoped: bool) -> str:
    """Optimized HLO of the K-round runner as ``run_training`` builds it:
    FedCET with shift:q8 over the arena on reduced fedlm-100m, the
    per-round loss as its metric, with or without sketch telemetry, and
    with ``telemetry.scope`` live or replaced by a null context."""
    cfg = get_config("fedlm-100m").reduced()
    model = build_model(cfg)
    grad_fn = jax.grad(model.loss)
    algo = FedScenario(compression="shift:q8", arena=True,
                       telemetry=TELEMETRY if telemetry else False).apply(
        FedCET(alpha=0.05, c=0.05, tau=TAU, n_clients=N_CLIENTS))
    tokens = jax.ShapeDtypeStruct((ROUNDS, TAU, N_CLIENTS, BATCH, SEQ),
                                  jnp.int32)

    def round_loss(s, b):
        last = jax.tree.map(lambda a: a[-1], b)
        return jnp.mean(jax.vmap(model.loss)(algo.client_params(s), last))

    patch = (contextlib.nullcontext() if scoped
             else mock.patch.object(tele, "scope", _null_scope))
    with patch:
        params = jax.eval_shape(model.init, jax.random.key(0))
        state = jax.eval_shape(
            lambda p, b: algo.init(grad_fn, p, b), params,
            {"tokens": jax.ShapeDtypeStruct(tokens.shape[2:], jnp.int32)})
        runner = make_round_runner(algo, grad_fn, metric_fn=round_loss,
                                   metric_with_batch=True,
                                   metric_before=True, donate=True)
        return runner.lower(state, {"tokens": tokens}).compile().as_text()


def _canonical(text: str) -> str:
    """The HLO text without metadata, each instruction and computation
    renamed by its order of appearance. The lowering numbers a name's
    uniquifying suffix as it goes, and a scope around a call of an inner
    jit (``jnp.quantile``'s ``_where``) shifts a few of those numbers."""
    text = _FRAME_TABLES.sub("", _METADATA.sub("", text))
    order: dict = {}
    for name in _NAME.findall(text):
        order.setdefault(name, f"n{len(order)}")
    return _NAME.sub(lambda m: "%" + order[m.group(1)], text)


def test_every_scope_reaches_op_name_metadata():
    text = _compiled_text(telemetry=True, scoped=True)
    found = {s for op in re.findall(r'op_name="([^"]*)"', text)
             for s in re.findall(r"fed\.[a-z_]+", op)}
    assert set(tele.SCOPES) <= found, set(tele.SCOPES) - found
    assert found <= set(tele.SCOPES), found - set(tele.SCOPES)


@pytest.mark.parametrize("telemetry", [True, False], ids=["tele", "bare"])
def test_scopes_change_only_metadata(telemetry):
    scoped = _compiled_text(telemetry, scoped=True)
    bare = _compiled_text(telemetry, scoped=False)
    assert "fed.client_grad" in scoped and "fed.client_grad" not in bare
    assert _canonical(scoped) == _canonical(bare)


def test_scope_rejects_unknown_names():
    with pytest.raises(ValueError, match="unknown scope"):
        tele.scope("fed.nowhere")


def test_run_training_trace_has_host_spans(tmp_path):
    from jax.profiler import ProfileData

    from repro.launch.train import run_training

    trace_dir = str(tmp_path / "trace")
    run_training("fedlm-100m", steps=2, n_clients=2, batch=2, seq_len=16,
                 compression="shift:q8", telemetry="memory",
                 trace_rounds="0:2", trace_dir=trace_dir)
    paths = glob.glob(os.path.join(trace_dir, "**", "*.xplane.pb"),
                      recursive=True)
    assert len(paths) == 1, paths
    data = ProfileData.from_file(paths[0])
    names = {e.name for plane in data.planes
             if plane.name.startswith("/host:")
             for line in plane.lines for e in line.events}
    want = {"segment_dispatch", "telemetry_drain", "results_fetch",
            "drain.device_get", "drain.comm_bits", "drain.events"}
    assert want <= names, want - names
