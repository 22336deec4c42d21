"""The expert layer's parts, the grouped products' roofline and the
exposed all-reduce time, on hand-made HLO text and hand-made traces."""

import types

import pytest

import moe_ops
import tracefile
from metrics import (allreduce_exposed_ms, moe_experts_ms,
                     moe_experts_roofline, moe_route_ms)

HLO = """HloModule jit_run, is_scheduled=true

%fused (param_0: f32[8]) -> f32[8] {
  %param_0 = f32[8]{0} parameter(0)
  ROOT %rd.1 = f32[8]{0} custom-call(%param_0), custom_call_target="tpu_custom_call", metadata={op_name="ragged-dot-none"}
}

ENTRY %main (p: f32[8]) -> f32[8] {
  %p = f32[8]{0} parameter(0)
  %while.3 = (s32[], f32[8]{0}) while(%tuple.0), condition=%cond, body=%body, metadata={op_name="jit(run)/while"}
  %sort.2 = f32[8]{0} sort(%p), metadata={op_name="jit(run)/fed.client_grad/vmap(jvp(moe.route))/sort"}
  %ragged-dot-none.4 = f32[8]{0} fusion(%p), kind=kCustom, calls=%fused, metadata={op_name="ragged-dot-none"}
  %fusion.5 = f32[8]{0} fusion(%p), kind=kLoop, calls=%fused, metadata={op_name="jit(run)/fed.client_grad/transpose(jvp(moe.experts))/mul"}
  %all-reduce.6 = f32[8]{0} all-reduce(%p), metadata={op_name="jit(run)/fed.aggregate/psum"}
  ROOT %add.7 = f32[8]{0} add(%p, %p), metadata={op_name="jit(run)/fed.aggregate/add"}
}
"""


def ctx(ops, rounds=2):
    job = types.SimpleNamespace(compiled=types.SimpleNamespace(
        as_text=lambda: HLO), held_slots=1000)
    tr = tracefile.Trace(devices=[ops], host=[], window=(100, 200))
    return {"job": job, "trace": tr, "rounds": rounds,
            "config": dict(d_model=4, d_ff=2, n_experts=2, n_layers=1,
                           remat=True),
            "traffic": dict(n_clients=1, tau=1),
            "peaks": dict(bf16_flops_per_s=1e12, hbm_bytes_per_s=1e12)}


def test_parts():
    m = moe_ops.part_map(HLO)
    assert m["%sort.2"] == "moe.route"
    assert m["%ragged-dot-none.4"] == "moe.experts"
    assert m["%fusion.5"] == "moe.experts"
    assert m["%add.7"] is None and m["%while.3"] == moe_ops.scopes.CONTAINER


def test_per_round_ms_and_roofline():
    c = ctx([("%while.3 = (s32[]) while(...)", 100, 200),
             ("%sort.2 = f32[8] sort(...)", 90, 110),
             ("%ragged-dot-none.4 = f32[8] fusion(...)", 110, 150),
             ("%fusion.5 = f32[8] fusion(...)", 150, 160),
             ("%add.7 = f32[8] add(...)", 160, 170)])
    assert moe_route_ms.read(c) == pytest.approx(1e-6 * 10 / 2)
    assert moe_experts_ms.read(c) == pytest.approx(1e-6 * 50 / 2)
    # 1000 slots x (12 + 3) products of 2*4*2 flops; (4+2)*4 bytes a slot
    # product and 2*4*2*4 bytes of weights in each of 2*(12+3) calls
    ops, nbytes = moe_experts_roofline.required(c["config"], c["traffic"],
                                                1000, 2)
    assert ops == 1000 * 15 * 16
    assert nbytes == 1000 * 15 * 24 + 30 * 64
    assert moe_experts_roofline.read(c) == pytest.approx(
        100 * nbytes / 1e12 / 50e-9)


def test_nothing_to_read():
    c = ctx([("%add.7 = f32[8] add(...)", 120, 130)])
    assert moe_route_ms.read(c) is None
    assert moe_experts_roofline.read(c) is None
    assert allreduce_exposed_ms.read(c) is None


def test_allreduce_exposed():
    # the all-reduce runs 120-160; the add overlaps 150-170 and the while
    # loop, a container, everything: 30 ns exposed over 3 rounds
    c = ctx([("%while.3 = (s32[]) while(...)", 100, 200),
             ("%all-reduce.6 = f32[8] all-reduce(...)", 120, 160),
             ("%add.7 = f32[8] add(...)", 150, 170)], rounds=3)
    assert allreduce_exposed_ms.read(c) == pytest.approx(1e-6 * 30 / 3)
