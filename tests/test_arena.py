"""Packed parameter arena: pack/unpack exactness, the arena round
lowering vs the per-leaf reference, and the fused round tail.

The arena (repro/core/arena.py) is pure data movement — reshape, zero
pad, concat — so pack/unpack must round-trip BITWISE, and an arena-run
round must match the per-leaf round <= 1e-12 (in f64 via conftest; in
practice most cells land bitwise) bare AND under the composed scenario
stack (shift:q8 x 0.8 participation x cohort), including a checkpoint
flipped between representations mid-sweep (``adapt_state``). The fused
tail (``FedCET(use_fused_kernel=True)`` + arena) replicates the generic
seam's PRNG schedule and masked-mean expressions, so it pins to the same
tolerance. Kernel parity: the Pallas kernels (interpret mode on CPU)
against their kernels/ref.py oracles.
"""

import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import (
    Arena,
    ArenaLayout,
    CohortSpec,
    FedAvg,
    Scaffold,
    adapt_state,
    pack,
    run_rounds,
    unpack,
    with_arena,
    with_cohort,
    with_compression,
    with_participation,
)
from repro.core.fedcet import FedCET
from repro.data.quadratic import make_hetero_hessian_problem

N, M, TAU, ROUNDS = 24, 7, 2, 4
TOL = 1e-12

PROB = make_hetero_hessian_problem(0, n_clients=N, dim=12, n_measurements=4)
GRAD = jax.grad(PROB.client_loss)
BATCHES = PROB.stacked_batches(TAU)
FIRST = jax.tree.map(lambda b: b[0], BATCHES)


def _algos():
    return {
        "fedcet": FedCET(alpha=0.02, c=0.3, tau=TAU, n_clients=N),
        "fedavg": FedAvg(alpha=0.05, tau=TAU, n_clients=N),
        "scaffold": Scaffold(alpha_l=0.02, tau=TAU, n_clients=N),
    }


def _composed(algo):
    """The issue's composed stack: shift:q8 x 0.8 participation x cohort."""
    algo = with_participation(algo, 0.8, seed=3)
    algo = with_compression(algo, compressor="shift:q8", seed=5)
    return with_cohort(algo, CohortSpec(size=M, selector="block"), seed=7)


def _run(algo, rounds=ROUNDS, state=None):
    if state is None:
        state = algo.init(GRAD, jnp.zeros((PROB.dim,), PROB.b.dtype), FIRST)
    final, _ = run_rounds(algo, GRAD, state, BATCHES, rounds=rounds)
    return final


def _assert_close(a, b, tol=TOL):
    la, lb = jax.tree.leaves(a), jax.tree.leaves(b)
    assert len(la) == len(lb)
    for x, y in zip(la, lb):
        assert float(jnp.max(jnp.abs(x - y))) <= tol


def _assert_equiv(arena_state, per_leaf_state, tol=TOL):
    """Adapt the arena-run state onto the per-leaf structure and compare."""
    _assert_close(adapt_state(arena_state, per_leaf_state),
                  per_leaf_state, tol=tol)


# --------------------------------------------------- pack/unpack round-trip
def _odd_tree(key, dtype=jnp.float64, lead=None):
    """Leaf sizes chosen to exercise lane padding: none divides 1024."""
    shapes = [("w", (3, 5)), ("b", (7,)), ("scalar", ()), ("big", (1030,)),
              ("nest_k", (2, 513))]
    ks = jax.random.split(key, len(shapes))
    mk = lambda k, s: jax.random.normal(  # noqa: E731
        k, ((lead,) + s if lead is not None else s), dtype)
    return {name: mk(k, s) for (name, s), k in zip(shapes, ks)}


def test_pack_unpack_roundtrip_bitwise():
    tree = _odd_tree(jax.random.key(0))
    lo = ArenaLayout.for_tree(tree)
    arena = pack(tree, lo)
    assert arena.data.shape == (lo.rows, 1024)
    back = unpack(arena)
    for a, b in zip(jax.tree.leaves(tree), jax.tree.leaves(back)):
        assert a.dtype == b.dtype and a.shape == b.shape
        assert bool(jnp.all(a == b))


def test_pack_unpack_roundtrip_stacked():
    tree = _odd_tree(jax.random.key(1), lead=5)
    lo = ArenaLayout.for_tree(_odd_tree(jax.random.key(1)))
    arena = pack(tree, lo)
    assert arena.data.shape == (5, lo.rows, 1024)
    back = unpack(arena)
    for a, b in zip(jax.tree.leaves(tree), jax.tree.leaves(back)):
        assert bool(jnp.all(a == b))


def test_pack_pads_are_zero():
    tree = {"b": jnp.ones((7,), jnp.float64)}
    arena = pack(tree)
    assert float(jnp.sum(arena.data)) == 7.0  # everything past n is 0


def test_layout_row_segments():
    tree = _odd_tree(jax.random.key(2))
    lo = ArenaLayout.for_tree(tree)
    seg = lo.row_segments()
    assert seg.shape == (lo.rows,)
    assert lo.rows % 8 == 0  # whole sublane tiles: the TPU layout stays tiled
    counts = np.bincount(seg, minlength=len(lo.shapes))
    assert tuple(counts) == lo.rows_per_leaf
    assert lo.num_params == sum(int(np.prod(s)) for s in lo.shapes)


def test_layout_rejects_bad_trees():
    with pytest.raises(ValueError):  # mixed dtypes
        ArenaLayout.for_tree({"a": jnp.ones((2,), jnp.float32),
                              "b": jnp.ones((2,), jnp.float64)})
    with pytest.raises(ValueError):  # non-float
        ArenaLayout.for_tree({"a": jnp.ones((2,), jnp.int32)})
    lo = ArenaLayout.for_tree({"a": jnp.ones((3,))})
    with pytest.raises(ValueError):  # wrong leaf count
        pack({"a": jnp.ones((3,)), "b": jnp.ones((3,))}, lo)
    with pytest.raises(ValueError):  # neither model- nor stacked-shaped
        pack({"a": jnp.ones((4, 4))}, lo)


def test_arena_is_transparent_pytree():
    tree = _odd_tree(jax.random.key(3))
    a = pack(tree)
    b = jax.tree.map(lambda x: 2.0 * x, a)
    assert isinstance(b, Arena) and b.layout is a.layout
    assert bool(jnp.all(b.data == 2.0 * a.data))
    sds = jax.eval_shape(lambda x: x, a)
    assert jax.tree.leaves(sds)[0].shape == a.data.shape


# ------------------------------------- arena == per-leaf, quadratic (f64)
@pytest.mark.parametrize("name", list(_algos()))
def test_arena_equiv_bare(name):
    algo = _algos()[name]
    _assert_equiv(_run(with_arena(algo)), _run(algo))


@pytest.mark.parametrize("name", list(_algos()))
def test_arena_equiv_composed(name):
    algo = _composed(_algos()[name])
    _assert_equiv(_run(with_arena(algo)), _run(algo))


def test_fused_tail_equiv():
    """use_fused_kernel=True routes the arena round through the fused tail
    (FedCET._fused_tail -> ops.fedcet_round_tail); must match both the
    generic arena path and the per-leaf reference, bare and masked."""
    def mk(fused, participation=None):
        a = FedCET(alpha=0.02, c=0.3, tau=TAU, n_clients=N,
                   use_fused_kernel=fused)
        a = with_compression(with_arena(a), compressor="shift:q8", seed=5)
        if participation is not None:
            a = with_participation(a, participation, seed=3)
        return a

    _assert_equiv(_run(mk(True)), _run(mk(False)))
    _assert_equiv(_run(mk(True, 0.8)), _run(mk(False, 0.8)))
    per_leaf = _run(with_compression(
        FedCET(alpha=0.02, c=0.3, tau=TAU, n_clients=N),
        compressor="shift:q8", seed=5))
    _assert_equiv(_run(mk(True)), per_leaf)


def test_server_aggregate_fused_flag_per_leaf():
    """Satellite: the kernel-backed ``FedCET.server_aggregate`` (the
    ``fedcet_comm`` pair with the compressed-message ``v=`` carry) matches
    the tree.map expression on the plain per-leaf path too."""
    mk = lambda fused: with_compression(  # noqa: E731
        FedCET(alpha=0.02, c=0.3, tau=TAU, n_clients=N,
               use_fused_kernel=fused), compressor="shift:q8", seed=5)
    _assert_close(_run(mk(True)), _run(mk(False)))


# --------------------------------------------- tiny transformer full round
def _tiny_lm():
    from repro.configs import get_config
    from repro.models import build_model

    cfg = dataclasses.replace(
        get_config("fedlm-100m").reduced(),
        d_model=32, n_heads=2, n_kv_heads=2, head_dim=16, d_ff=64,
        vocab_size=96)
    return build_model(cfg), cfg


@pytest.mark.parametrize("compose", [False, True])
def test_arena_equiv_tiny_transformer(compose):
    """Full LM rounds on a tiny transformer (f32 model dtypes): arena vs
    per-leaf. The lowering is pure data movement around identical math, so
    the pin is far below f32 training noise."""
    from repro.data.synthetic import make_hetero_lm_dataset

    model, cfg = _tiny_lm()
    nc, tau, b, s = 5, 2, 2, 8
    params = model.init(jax.random.key(0))
    ds = make_hetero_lm_dataset(cfg.vocab_size, nc, s, b, seed=0)
    batches = {"tokens": ds.sample_round(0, tau)}
    grad_fn = jax.grad(model.loss)

    def run(algo, rounds=3):
        st = algo.init(grad_fn, params,
                       jax.tree.map(lambda x: x[0], batches))
        fin, _ = run_rounds(algo, grad_fn, st, batches, rounds=rounds)
        return fin

    algo = FedCET(alpha=3e-3, c=0.05, tau=tau, n_clients=nc)
    if compose:
        algo = with_participation(
            with_compression(algo, compressor="shift:q8", seed=5), 0.8,
            seed=3)
    pl_state = run(algo)
    ar_state = run(with_arena(algo))
    _assert_close(adapt_state(ar_state, pl_state), pl_state, tol=1e-5)


# ------------------------------------------------- checkpoint/resume flips
def test_checkpoint_flips_between_representations(tmp_path):
    """Save a per-leaf checkpoint mid-sweep, resume it as an ``--arena``
    run (and the reverse): both finish <= 1e-12 of the straight runs."""
    from repro.checkpoint.ckpt import load_pytree, save_pytree

    base = _composed(FedCET(alpha=0.02, c=0.3, tau=TAU, n_clients=N))
    arena = with_arena(base)

    straight = _run(base, rounds=6)
    # per-leaf -> arena
    mid = _run(base, rounds=3)
    path = str(tmp_path / "per_leaf.npz")
    save_pytree(path, mid)
    like = arena.init(GRAD, jnp.zeros((PROB.dim,), PROB.b.dtype), FIRST)
    resumed = adapt_state(load_pytree(path, mid), like)
    final = _run(arena, rounds=3, state=resumed)
    _assert_equiv(final, straight)
    # arena -> per-leaf (also exercises checkpointing an Arena state)
    mid_a = _run(arena, rounds=3)
    path_a = str(tmp_path / "arena.npz")
    save_pytree(path_a, mid_a)
    resumed_pl = adapt_state(load_pytree(path_a, mid_a), mid)
    final_pl = _run(base, rounds=3, state=resumed_pl)
    _assert_close(final_pl, straight)


# --------------------------------------------------- kernel == ref parity
def test_fedcet_comm_kernel_matches_ref_with_v():
    from repro.kernels import ops as kops

    k = jax.random.split(jax.random.key(7), 4)
    shape = (1000,)  # odd: exercises the tile padding
    d, m, v = (jax.random.normal(k[i], shape) for i in range(3))
    mb = jax.random.normal(k[3], shape)
    for vv in (None, v):
        ker = kops.fedcet_comm(d, m, mb, 0.3, 0.02, v=vv, impl="kernel")
        ref = kops.fedcet_comm(d, m, mb, 0.3, 0.02, v=vv, impl="ref")
        _assert_close(ker, ref)


@pytest.mark.parametrize("c,rows", [(3, 5), (30, 13)])  # 30: lane tiles
def test_round_tail_kernel_matches_ref(c, rows):
    from repro.kernels import ops as kops

    ks = jax.random.split(jax.random.key(8), 5)
    v = jax.random.normal(ks[0], (c, rows, 1024))
    h = jax.random.normal(ks[1], (c, rows, 1024))
    d = jax.random.normal(ks[2], (c, rows, 1024))
    u = jax.random.uniform(ks[3], (rows, 1024))
    scale = jnp.max(jnp.abs(v - h), axis=(0, 2))[:, None] / 127.0
    scale = scale.at[2, 0].set(0.0)  # a zero-scale (constant-leaf) row
    w = jax.random.bernoulli(ks[4], 0.7, (c, 1)).astype(v.dtype)
    den = jnp.maximum(jnp.sum(w), 1.0).reshape(1, 1)
    args = dict(c=0.3, alpha=0.02, beta=0.5, bits=8)
    ref = kops.fedcet_round_tail(v, h, d, u, scale, w, den, impl="ref",
                                 **args)
    for impl in ("kernel", "auto"):
        got = kops.fedcet_round_tail(v, h, d, u, scale, w, den, impl=impl,
                                     **args)
        _assert_close(got, ref)


def test_stochastic_quantize_rows_matches_oracle():
    from repro.kernels import ops as kops

    rows = 9
    ks = jax.random.split(jax.random.key(9), 2)
    a = jax.random.normal(ks[0], (rows, 1024))
    u = jax.random.uniform(ks[1], (rows, 1024))
    scale = jnp.max(jnp.abs(a), axis=1, keepdims=True) / 127.0
    got = kops.stochastic_quantize_rows(a, u, scale, bits=8)
    inv = jnp.where(scale > 0, 1.0 / scale, 0.0)
    want = jnp.clip(jnp.floor(a * inv + u), -127, 127) * scale
    _assert_close(got, want)
