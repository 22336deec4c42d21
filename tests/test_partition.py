"""Partitioning rules + a small-mesh end-to-end lowering test.

The big-mesh dry-run lives in its own process (it forces 512 host devices);
here we check the PartitionSpec rule table directly, and run one miniature
lowering on a 4-device subprocess mesh to catch rule/shape regressions
inside the normal pytest run.
"""

import subprocess
import sys

import jax
import pytest
from jax.sharding import PartitionSpec as P

from repro.launch.partition import _base_spec, param_pspec


class L:  # tiny ShapeDtypeStruct stand-in
    def __init__(self, *shape):
        self.shape = shape
        self.ndim = len(shape)


TP = 16


def test_attention_projection_rules():
    assert _base_spec(("layers", "attn", "wq"), (6144, 6144), TP) == (None, "model")
    assert _base_spec(("layers", "attn", "wo"), (6144, 6144), TP) == ("model", None)
    assert _base_spec(("layers", "attn", "wk"), (2048, 256), TP) == (None, "model")


def test_moe_rules_divisible_vs_not():
    # llama4: 16 experts over a 16-way model axis -> expert parallel
    assert _base_spec(("layers", "moe", "up"), (16, 5120, 8192), TP) == ("model", None, None)
    # granite: 40 experts don't divide 16 -> shard the ffn dim instead
    assert _base_spec(("layers", "moe", "up"), (40, 1536, 512), TP) == (None, None, "model")
    assert _base_spec(("layers", "moe", "down"), (40, 512, 1536), TP) == (None, "model", None)
    # shared expert inside the moe dict follows dense rules
    assert _base_spec(("layers", "moe", "shared", "up"), (5120, 8192), TP) == (None, "model")


def test_embed_vocab_sharding_and_odd_vocab():
    assert _base_spec(("embed",), (92544, 6144), TP) == ("model", None)
    # odd vocab (49155) is not sharded
    assert _base_spec(("embed",), (49155, 1536), TP) == (None, None)


def test_norms_replicated():
    assert _base_spec(("layers", "ln1", "weight"), (6144,), TP) == ()


def test_stacked_and_client_axes_padding():
    # federated state leaf: [clients, L, d_in, d_out]
    spec = param_pspec(("layers", "attn", "wq"), L(16, 48, 6144, 6144), TP,
                       client_axes=("pod", "data"))
    assert spec == P(("pod", "data"), None, None, "model")
    spec = param_pspec(("layers", "mlp", "up"), L(48, 2048, 6144), TP)
    assert spec == P(None, None, "model")


def test_fsdp_extra_axis():
    spec = param_pspec(("layers", "attn", "wq"), L(4, 48, 5120, 5120), TP,
                       client_axes=("data",), extra_axis="fsdp", extra_size=4)
    assert spec == P(("data",), None, "fsdp", "model")
    # 1-d leaves unaffected
    spec = param_pspec(("layers", "ln1", "weight"), L(48, 5120), TP,
                       extra_axis="fsdp", extra_size=4)
    assert spec == P(None, None)


SMALL_MESH_SCRIPT = r"""
import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
import jax
import numpy as np
from repro.launch.mesh import make_test_mesh
from repro.launch.train import make_plan, lower_train_step, TrainPlan
from repro.launch import serve
import dataclasses
from repro.configs import get_config
from repro.configs.base import INPUT_SHAPES, ShapeConfig

mesh = make_test_mesh((2, 4), ("data", "model"))

# miniature shapes so the 8-device CPU compile is fast
INPUT_SHAPES["train_4k"] = ShapeConfig("train_4k", 64, 4, "train")
INPUT_SHAPES["decode_32k"] = ShapeConfig("decode_32k", 64, 4, "decode")

from repro.configs import registry
import repro.configs as C
cfg = get_config("qwen3-1.7b").reduced()
reg = registry()
reg["qwen3-1.7b"] = dataclasses.replace(cfg, name="qwen3-1.7b")

plan = make_plan("qwen3-1.7b", mesh)
compiled = lower_train_step(plan).compile()
assert compiled.memory_analysis().temp_size_in_bytes > 0
print("TRAIN_OK")

lowered = serve.lower_decode("qwen3-1.7b", mesh, shape_name="decode_32k")
lowered.compile()
print("DECODE_OK")
"""


def test_small_mesh_lowering_subprocess():
    """End-to-end pjit lowering on a 2x4 fake-device mesh (own process so the
    device-count flag doesn't leak into this test session)."""
    res = subprocess.run(
        [sys.executable, "-c", SMALL_MESH_SCRIPT],
        capture_output=True, text=True, timeout=900,
        env={"PYTHONPATH": "src", "PATH": "/usr/bin:/bin",
             # skip the 60s TPU-backend probe; this is a fake-device CPU test
             "JAX_PLATFORMS": "cpu"},
        cwd=__file__.rsplit("/tests/", 1)[0],
    )
    assert "TRAIN_OK" in res.stdout, res.stderr[-2000:]
    assert "DECODE_OK" in res.stdout, res.stderr[-2000:]


MOE_MESH_SCRIPT = r"""
import os
import sys
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
import dataclasses
import json
import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P
from repro.configs import get_config, registry
from repro.configs.base import INPUT_SHAPES, ShapeConfig
from repro.launch.mesh import make_test_mesh
from repro.launch.train import lower_train_step, make_plan
from repro.models.moe import apply_moe, init_moe
from repro.utils.sharding_ctx import activation_sharding

E = int(sys.argv[1])
mesh = make_test_mesh((2, 4), ("data", "model"))
INPUT_SHAPES["train_4k"] = ShapeConfig("train_4k", 64, 4, "train")
arch = "granite-moe-3b-a800m"
cfg = dataclasses.replace(get_config(arch).reduced(), n_experts=E,
                          router_experts=E, capacity_factor=float(E))
registry()[arch] = cfg
lower_train_step(make_plan(arch, mesh)).compile()
print("TRAIN_OK")

# the capacity dispatch under the mesh lowering's context against the
# drop-free grouped layer off it, on the same weights and tokens
params = init_moe(jax.random.key(0), cfg.d_model, cfg.d_ff, E, jnp.float32,
                  shared_expert=False, activation=cfg.activation)
x = jax.random.normal(jax.random.key(1), (4, 64, cfg.d_model), jnp.float32)
kw = dict(n_experts=E, k=cfg.experts_per_token,
          capacity_factor=cfg.capacity_factor, activation=cfg.activation,
          shared_expert=False)
layer = jax.jit(lambda p, a: apply_moe(p, a, **kw))
want = layer(params, x)
# the launcher's token-sharded dispatch when experts don't divide `model`
moe = None if E % 4 == 0 else {"nb": 1, "ns": 4, "axes": ("model",),
                               "spec": P("model", None, None)}
with mesh, activation_sharding(residual=P(None, "model", None),
                               logits=P(None, None, "model"),
                               moe_shards=moe):
    got = jax.jit(lambda p, a: apply_moe(p, a, **kw))(params, x)
scale = float(jnp.max(jnp.abs(want[0])))
print(json.dumps({"out_err": float(jnp.max(jnp.abs(got[0] - want[0]))) / scale,
                  "aux": [float(got[1]), float(want[1])],
                  "counts": [got[2].tolist(), want[2].tolist()]}))
"""


@pytest.mark.parametrize("n_experts", [4, 3], ids=["capacity", "token-sharded"])
def test_moe_mesh_dispatch_matches_dropfree_subprocess(n_experts):
    """A reduced expert model's round compiles on a 2x4 fake-device mesh,
    and there its capacity dispatch (plain, and token-sharded when the
    experts don't divide the model axis) gives the drop-free layer's
    output at a capacity that drops nothing."""
    import json

    res = subprocess.run(
        [sys.executable, "-c", MOE_MESH_SCRIPT, str(n_experts)],
        capture_output=True, text=True, timeout=900,
        env={"PYTHONPATH": "src", "PATH": "/usr/bin:/bin",
             "JAX_PLATFORMS": "cpu"},
        cwd=__file__.rsplit("/tests/", 1)[0],
    )
    assert "TRAIN_OK" in res.stdout, res.stderr[-2000:]
    out = json.loads(res.stdout.strip().splitlines()[-1])
    # float32 sums in another order: a few ulps of the output's scale
    assert out["out_err"] < 1e-5, out
    got, want = out["counts"]
    assert got == want and sum(want) == 4 * 64 * 2, out
    if n_experts % 4 == 0:  # one token stream: the same load-balance term
        assert abs(out["aux"][0] - out["aux"][1]) < 1e-6, out
