"""The main path's Pallas kernels, compiled by the TPU compiler for a
described v5e chip that is not attached.

Interpret mode (every other kernel test) accepts what Mosaic refuses: a
block whose last two dims are not (8, 128)-aligned, or a grid step that
needs more VMEM than the scoped limit. These compiles catch both at
fedlm-100m's published widths. The expert layer's grouped product is
compiled too, at granite-moe-3b-a800m's widths, under the engine's vmap
over clients and the gradient: the TPU's ragged dot refuses a batch
dimension, which the CPU's accepts. One FedCET round over the arena is
compiled whole, at a small size, for the layout in which the packed
gradient meets the local update. Nothing runs, so they say nothing
about results or times.

The topology is described inside a module fixture, never at import: only
one process may load the TPU library, and under several test workers only
the one given this file should.
"""

import re

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from repro.configs import get_config
from repro.core.arena import LANES, ArenaLayout
from repro.kernels import fedcet_update as KF
from repro.kernels import flash_attention as KA
from repro.kernels import gossip_reduce as KG
from repro.kernels import ops
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
        return text
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


@pytest.mark.parametrize("clients", [2, 3, 4, 13, 16])
def test_client_sketch_compiles(compile_for_chip, rows, clients):
    compile_for_chip(
        lambda x: KT.client_sketch_3d(x, bins=48, lo=-12.0, hi=4.0,
                                      interpret=False),
        ((clients, rows, LANES), F32))


def test_client_sketch_reads_the_arena_in_place(compile_for_chip, rows,
                                                monkeypatch):
    """The wrapper hands the kernel the arena buffer in its own layout:
    no relayout copy and no pad of the store feeds the custom call."""
    monkeypatch.setattr(ops, "_interpret", lambda: False)
    sketch = ops.telemetry_sketch.__wrapped__  # traced here, not cached
    text = compile_for_chip(
        lambda x: sketch(x, bins=48, lo=-12.0, hi=4.0, k=4, impl="kernel"),
        ((4, rows, LANES), F32))
    call = next(ln for ln in text.splitlines() if "tpu_custom_call" in ln)
    arg = re.search(r"custom-call\((%[\w.-]+)\)", call).group(1)
    (defn,) = [ln for ln in text.splitlines()
               if ln.strip().startswith(arg + " = ")]
    assert "parameter(0)" in defn, defn
    assert f"f32[4,{rows},1024]{{2,1,0:T(8,128)}}" in defn, defn
    assert " copy(" not in text and " pad(" not in text


def test_grouped_matmul_compiles_vmapped_with_its_gradient(compile_for_chip):
    from repro.models.moe import grouped_matmul

    # two clients' routed slots (4 x 512 tokens, 8 a token), 10 experts
    # of 1536 x 512
    def grads(rows, w, sizes):
        loss = lambda r, w, s: jnp.sum(grouped_matmul(r, w, s) ** 2)  # noqa: E731
        return jax.vmap(jax.grad(loss, argnums=(0, 1)))(rows, w, sizes)

    compile_for_chip(grads, ((2, 16384, 1536), F32), ((2, 10, 1536, 512), F32),
                     ((2, 10), jnp.int32))


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


def _arena_round_hlo(one_chip, params, loss, batch_shapes, n, tau=2):
    """Optimized HLO of one FedCET round over the arena at ``n`` clients,
    compiled for the described chip; ``params`` maps leaf names to
    shapes, ``batch_shapes`` batch keys to one client's batch shape."""
    from repro.core.engine import make_round_runner, with_arena
    from repro.core.fedcet import FedCET

    params = {k: jax.ShapeDtypeStruct(s, F32) for k, s in params.items()}
    algo = with_arena(FedCET(alpha=0.05, c=0.05, tau=tau, n_clients=n))
    grad_fn = jax.grad(loss)
    batches = {k: jax.ShapeDtypeStruct((1, tau, n) + s, F32,
                                       sharding=one_chip)
               for k, s in batch_shapes.items()}
    first = jax.tree.map(lambda a: jax.ShapeDtypeStruct(a.shape[2:], F32),
                         batches)
    with jax.enable_x64(False):
        state = jax.eval_shape(lambda p, b: algo.init(grad_fn, p, b),
                               params, first)
        state = jax.tree.map(lambda a: jax.ShapeDtypeStruct(
            a.shape, a.dtype, sharding=one_chip), state)
        return make_round_runner(algo, grad_fn, donate=True).lower(
            state, batches).compile().as_text()


def _instructions(text):
    """``{name: definition}`` of every instruction of an HLO text."""
    defs = {}
    for ln in text.splitlines():
        m = re.match(r"\s*(?:ROOT )?(%[\w.-]+) = (.*)", ln)
        if m:
            defs[m.group(1)] = m.group(2)
    return defs


def test_local_update_reads_the_packed_gradient_in_place(one_chip,
                                                        no_compile_cache):
    """A FedCET round over the arena at 4 clients: the packed gradient
    comes out in the x and d arenas' ``[clients, rows, 1024]`` layout, so
    ``v = x - a*g - a*d`` is one fusion that reads it as it is. Packed as
    one flat ``[clients, rows * 1024]`` buffer, it is scaled there in a
    pass of its own and copied into the arenas' tiling inside the
    update. The last leaf (``w2``) takes the zero rows up to the sublane
    tile, and those are padded as rows, not in its flat shape."""
    n, d_in, hidden, d_out = 4, 256, 1000, 300
    params = {"w1": (d_in, hidden), "b1": (hidden,), "w2": (hidden, d_out),
              "b2": (d_out,)}

    def loss(p, b):
        h = jnp.tanh(b["x"] @ p["w1"] + p["b1"])
        return jnp.mean((h @ p["w2"] + p["b2"] - b["y"]) ** 2)

    text = _arena_round_hlo(one_chip, params, loss,
                            {"x": (8, d_in), "y": (8, d_out)}, n)
    layout = ArenaLayout.for_tree(
        {k: jax.ShapeDtypeStruct(s, F32) for k, s in params.items()})
    rows, last = layout.rows, layout.rows_per_leaf[-1]
    defs = _instructions(text)
    for flat in (f"f32[{n},{rows * LANES}]", f"f32[{n},{last * LANES}]"):
        assert not [k for k, v in defs.items() if v.startswith(flat)], flat
    updates = [v for v in defs.values()
               if v.startswith(f"f32[{n},{rows},{LANES}]")
               and " fusion(" in v and "fed.local_update/sub" in v]
    assert updates
    for upd in updates:
        body = re.search(r"calls=(%[\w.-]+)", upd).group(1)
        comp = re.search(rf"^{re.escape(body)} .*?^}}", text, re.M | re.S)
        assert " copy(" not in comp.group(0), comp.group(0)
        args = re.search(r" fusion\((.*?)\), kind=", upd).group(1)
        for arg in re.findall(r"%[\w.-]+", args):
            assert " copy(" not in defs[arg], defs[arg]


def test_layer_stacked_gradient_is_packed_without_a_copy(one_chip,
                                                         no_compile_cache):
    """A model whose layers are one stacked weight, applied by a scan:
    the scan's backward writes that weight's gradient layer by layer. Its
    pack pins it row-major, so the compiler lays the scan's output out
    ``[clients, layers, ...]`` and hands it to the pack as it is; left
    free, it keeps it layers-major and copies it into row-major inside
    every gradient call."""
    n, layers, width = 4, 3, 512
    params = {"w": (layers, width, width), "b": (layers, width)}

    def loss(p, b):
        def layer(h, wb):
            return jnp.tanh(h @ wb[0] + wb[1]), None

        h, _ = jax.lax.scan(layer, b["x"], (p["w"], p["b"]))
        return jnp.mean((h - b["y"]) ** 2)

    text = _arena_round_hlo(one_chip, params, loss,
                            {"x": (8, width), "y": (8, width)}, n)
    stacked = f"f32[{n},{layers},{width},{width}]"
    copies = [v for v in _instructions(text).values()
              if v.startswith(stacked) and " copy(" in v]
    assert not copies, copies


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
