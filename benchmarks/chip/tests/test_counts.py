"""FLOPs per training token and the sketch's required bytes against hand
counts at tiny sizes."""

import pytest

from flops import dense, ssm
from metrics.client_sketch_roofline import required_bytes


def test_dense_flops_by_hand():
    cfg = dict(n_layers=2, d_model=8, n_heads=2, n_kv_heads=1, head_dim=4,
               d_ff=16, vocab_size=32)
    s = 4
    # per token and layer: q 8x8, k 8x4, v 8x4, o 8x8, mlp 3 x 8x16
    mm = 64 + 32 + 32 + 64 + 3 * 128
    attn = 2 * s * 8                       # q.k and p.v over 4 keys
    fwd_seq = 2 * s * (2 * mm + 2 * attn) + (s - 1) * 2 * 8 * 32
    assert dense.train_flops_per_token(cfg, s) == pytest.approx(
        3 * fwd_seq / s)


def test_ssm_flops_by_hand():
    cfg = dict(n_layers=1, d_model=4, ssm_state=2, ssm_headdim=2,
               ssm_expand=2, ssm_conv=4, ssd_chunk=2, vocab_size=10)
    s = 4
    # d_inner 8, 4 heads of 2; projections z, x (4x8 each), B, C (4x2),
    # dt (4x4), out (8x4)
    proj = 2 * (32 + 32 + 8 + 8 + 16 + 32)
    conv = 2 * 4 * 12
    mixer = 2 * 2 * 2 + 2 * 2 * 4 * 2 + 2 * 2 * 4 * 2 * 2
    fwd_seq = s * (proj + conv + mixer) + (s - 1) * 2 * 4 * 10
    assert ssm.train_flops_per_token(cfg, s) == 3 * fwd_seq / s


def test_sketch_bytes():
    assert required_bytes(4, 1000) == 16000
