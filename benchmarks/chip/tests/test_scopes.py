"""Attribution of device ops to the program's layer scopes, on hand-made
HLO text and a hand-made trace."""

import importlib

import pytest

import scopes
import tracefile

HLO = """HloModule jit_run, is_scheduled=true

%fused_computation (param_0: f32[8]) -> f32[8] {
  %param_0 = f32[8]{0} parameter(0)
  ROOT %mul.3 = f32[8]{0} multiply(%param_0, %param_0), metadata={op_name="jit(run)/while/body/fed.local_update/mul"}
}

%body (arg: (s32[], f32[8])) -> (s32[], f32[8]) {
  %arg = (s32[], f32[8]{0}) parameter(0)
  %fusion.7 = f32[8]{0} fusion(%gte.1), kind=kLoop, calls=%fused_computation, metadata={op_name="jit(run)/while/body/fed.local_update/while/body/transpose(jvp(fed.client_grad))/dot_general" stack_frame_id=3}
  %fusion.8 = f32[8]{0} fusion(%fusion.7), kind=kLoop, calls=%fused_computation, metadata={op_name="jit(run)/while/body/fed.local_update/sub"}
  %copy.2 = f32[8]{0} copy(%fusion.8)
  %telemetry_sketch.4 = (f32[4,1]{1,0}, s32[1,128]{1,0}) custom-call(f32[4,8]{1,0} %bitcast.1), custom_call_target="tpu_custom_call", metadata={op_name="jit(run)/while/body/fed.telemetry/jit(telemetry_sketch)/pallas_call"}
  %loss.1 = f32[] reduce(%fusion.8), metadata={op_name="jit(run)/while/body/fed.round_loss/reduce_sum"}
  ROOT %tuple.1 = (s32[], f32[8]{0}) tuple(%gte.0, %copy.2)
}

ENTRY %main (p: f32[8]) -> f32[8] {
  %p = f32[8]{0} parameter(0)
  %while.643 = (s32[], f32[8]{0}) while(%tuple.0), condition=%cond, body=%body, metadata={op_name="jit(run)/while"}
  %agg.1 = f32[8]{0} add(%p, %p), metadata={op_name="jit(run)/while/body/fed.aggregate/add"}
  ROOT %gte.9 = f32[8]{0} get-tuple-element(%while.643), index=1
}
"""


def trace(devices=None):
    """Window 100..200 ns on chip 0: a while loop around everything, the
    ops of its body, an op that starts before the window and one that
    ends after it; chip 1 runs one long unscoped op."""
    ops = devices or [
        ("%while.643 = (s32[], f32[8]{0}) while(...)", 100, 200),
        ("%fusion.7 = f32[8]{0} fusion(...)", 100, 130),
        ("%fusion.8 = f32[8]{0} fusion(...)", 130, 140),
        ("%copy.2 = f32[8]{0} copy(...)", 140, 145),
        ("%telemetry_sketch.4 = (f32[4,1]{1,0}) custom-call(...)", 145, 165),
        ("%loss.1 = f32[] reduce(...)", 165, 170),
        ("%agg.1 = f32[8]{0} add(...)", 90, 110),
        ("%fusion.7 = f32[8]{0} fusion(...)", 190, 230),
        ("%fusion.99 = f32[8]{0} fusion(...)", 300, 310),
    ]
    return tracefile.Trace(devices=[ops, [("%fusion.2 = f32[2]", 0, 300)]],
                           host=[], window=(100, 200))


def test_innermost_scope_wins_and_containers_are_marked():
    m = scopes.scope_map(HLO)
    assert m["%fusion.7"] == "fed.client_grad"
    assert m["%fusion.8"] == "fed.local_update"
    assert m["%mul.3"] == "fed.local_update"
    assert m["%telemetry_sketch.4"] == "fed.telemetry"
    assert m["%loss.1"] == "fed.round_loss"
    assert m["%agg.1"] == "fed.aggregate"
    assert m["%while.643"] == scopes.CONTAINER
    assert m["%copy.2"] is None and m["%gte.9"] is None


def test_layer_time_clips_to_the_window_and_skips_containers():
    by = scopes.layer_ns(trace(), scopes.scope_map(HLO))
    # the while (100 ns over the whole window) is not counted; fusion.7
    # runs 100-130 and 190-200 of its 190-230; agg.1 only 100-110.
    assert by == {"fed.client_grad": 40, "fed.local_update": 10,
                  None: 5, "fed.telemetry": 20, "fed.round_loss": 5,
                  "fed.aggregate": 10}


class Job:
    compiled = None


@pytest.fixture
def ctx(monkeypatch):
    monkeypatch.setattr(scopes, "module_text", lambda compiled: HLO)
    return {"trace": trace(), "job": Job(), "rounds": 2}


def test_metrics_add_up_to_leaf_time(ctx):
    names = ["client_grad_ms", "arena_pack_ms", "local_update_ms",
             "uplink_ms", "aggregate_ms", "telemetry_ms", "round_loss_ms"]
    ms = {n: importlib.import_module(f"metrics.{n}").read(ctx)
          for n in names}
    assert ms["client_grad_ms"] == pytest.approx(40e-6 / 2)
    assert ms["arena_pack_ms"] == 0.0
    share = importlib.import_module("metrics.unscoped_share").read(ctx)
    leaf_ns = 90
    scoped_ns = sum(ms.values()) * ctx["rounds"] * 1e6
    assert scoped_ns == pytest.approx(85)
    assert scoped_ns + share / 100 * leaf_ns == pytest.approx(leaf_ns)


def test_a_program_without_scopes_reports_nothing(monkeypatch):
    bare = HLO.replace("fed.", "")
    monkeypatch.setattr(scopes, "module_text", lambda compiled: bare)
    ctx = {"trace": trace(), "job": Job(), "rounds": 2}
    assert importlib.import_module("metrics.telemetry_ms").read(ctx) is None
    assert importlib.import_module("metrics.unscoped_share").read(ctx) is None


def test_module_text_of_a_compiled_program():
    import jax
    import jax.numpy as jnp

    def f(x):
        with jax.named_scope("fed.client_grad"):
            y = jnp.sin(x) * 2
        return jnp.cumsum(y)

    compiled = jax.jit(f).lower(jnp.ones(8)).compile()

    class WithoutMetadata:
        """A compiled program whose text leaves the metadata out."""

        def as_text(self):
            return compiled.as_text().replace("op_name=", "")

        def runtime_executable(self):
            return compiled.runtime_executable()

    for program in (compiled, WithoutMetadata()):
        m = scopes.scope_map(scopes.module_text(program))
        assert "fed.client_grad" in m.values()
