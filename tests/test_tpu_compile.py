"""The main path's Pallas kernels, compiled by the TPU compiler for a
described v5e chip that is not attached.

Interpret mode (every other kernel test) accepts what Mosaic refuses: a
block whose last two dims are not (8, 128)-aligned, or a grid step that
needs more VMEM than the scoped limit. These compiles catch both at
fedlm-100m's published widths. Nothing runs, so they say nothing about
results or times.

The topology is described inside a module fixture, never at import: only
one process may load the TPU library, and under several test workers only
the one given this file should.
"""

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from repro.configs import get_config
from repro.core.arena import LANES, ArenaLayout
from repro.kernels import fedcet_update as KF
from repro.kernels import flash_attention as KA
from repro.kernels import gossip_reduce as KG
from repro.kernels import quantize as KQ
from repro.kernels import telemetry_reduce as KT
from repro.models import build_model

CFG = get_config("fedlm-100m")
F32 = jnp.float32


@pytest.fixture(scope="module")
def topo():
    from jax.experimental import topologies

    try:
        return topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:  # no TPU compiler in this installation
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")


@pytest.fixture(scope="module")
def one_chip(topo):
    return SingleDeviceSharding(topo.devices[0])


@pytest.fixture(scope="module")
def no_compile_cache():
    """A compile for a described chip is written to the persistent cache
    but cannot be read back without the chip; keep the cache off here."""
    from jax.experimental.compilation_cache import compilation_cache as cc

    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    cc.reset_cache()
    yield
    jax.config.update("jax_enable_compilation_cache", was)
    cc.reset_cache()


@pytest.fixture(scope="module")
def rows():
    """Rows of fedlm-100m's packed parameter arena (~107M f32 / 1024)."""
    model = build_model(CFG)
    return ArenaLayout.for_tree(
        jax.eval_shape(model.init, jax.random.key(0))).rows


@pytest.fixture
def compile_for_chip(one_chip, no_compile_cache):
    def compile_(fn, *shapes):
        args = [jax.ShapeDtypeStruct(s, dt, sharding=one_chip)
                for s, dt in shapes]
        # the program runs in 32-bit mode (conftest turns x64 on for the
        # convergence tests); Mosaic rejects 64-bit grid indices.
        with jax.enable_x64(False):
            text = jax.jit(fn).lower(*args).compile().as_text()
        assert "tpu_custom_call" in text  # the Mosaic kernel, not a fallback
    return compile_


# The f32 state of 16+ fedlm-100m clients exceeds one chip's HBM, so those
# compile over rows / clients (one model's worth of state in all); the
# tiling depends on the client count.
@pytest.mark.parametrize("clients,row_div", [(3, 1), (4, 1), (16, 16),
                                             (64, 64), (256, 256)])
def test_round_tail_compiles(compile_for_chip, rows, clients, row_div):
    r = rows // row_div
    compile_for_chip(
        lambda v, h, d, u, s, w, den: KF.fedcet_round_tail_3d(
            v, h, d, u, s, w, den, c=0.05, alpha=1e-2, beta=0.5, bits=8,
            interpret=False),
        ((clients, r, LANES), F32), ((clients, r, LANES), F32),
        ((clients, r, LANES), F32), ((r, LANES), F32), ((r, 1), F32),
        ((clients, 1), F32), ((1, 1), F32))


def test_quantize_rows_compiles(compile_for_chip, rows):
    compile_for_chip(
        lambda a, u, s: KQ.stochastic_quantize_rows_2d(a, u, s, bits=8,
                                                       interpret=False),
        ((rows, LANES), F32), ((rows, LANES), F32), ((rows, 1), F32))


@pytest.mark.parametrize("clients", [3, 16])
def test_client_sketch_compiles(compile_for_chip, rows, clients):
    compile_for_chip(
        lambda x: KT.client_sketch_2d(x, bins=48, lo=-12.0, hi=4.0,
                                      n_valid=clients, interpret=False),
        ((clients, rows * LANES), F32))


def test_gossip_reduce_compiles(compile_for_chip, rows):
    # 8 nodes x 3 slots over an eighth of the model's coordinates
    compile_for_chip(
        lambda x: KG.segment_reduce_2d(x, slots=3, interpret=False),
        ((8 * 3, rows * LANES // 8), F32))


@pytest.mark.parametrize("batch,seq,dtype", [(8, 128, F32),
                                             (2, 4096, jnp.bfloat16)])
def test_flash_attention_compiles(compile_for_chip, batch, seq, dtype):
    hq, hkv, hd = CFG.n_heads, CFG.n_kv_heads, CFG.head_dim
    compile_for_chip(
        lambda q, k, v: KA.flash_attention(q, k, v, interpret=False),
        ((batch, seq, hq, hd), dtype), ((batch, seq, hkv, hd), dtype),
        ((batch, seq, hkv, hd), dtype))


@pytest.mark.parametrize("clients", [1, 2, 3, 4, 7, 16, 30, 64, 171, 256,
                                     1000])
def test_round_tail_blocks_are_aligned(clients):
    """The tail's blocks obey Mosaic's tiling rule at any client count:
    rows a multiple of 8 (or all of them), lanes a multiple of 128 that
    divides the 1024-lane row, and the double-buffered blocks within the
    budget wherever one (8, 128) tile per client allows it."""
    for rows in (5, 8, 1000, 104504):
        rb, lb = KF.tail_blocks(clients, rows, 4)
        assert rb == rows or rb % 8 == 0
        assert lb % 128 == 0 and LANES % lb == 0
        per_elem = 2 * (6 * clients + 1) * 4
        if per_elem * 8 * 128 <= KF.TAIL_BLOCK_BUDGET:
            assert per_elem * rb * lb <= KF.TAIL_BLOCK_BUDGET
