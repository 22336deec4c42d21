"""Model FLOPs of one training token of the Mamba-2 LM.

Matrix-multiply operations (2 per multiply-add) of the forward pass, times
3 for forward plus backward; nothing recomputed is counted. Per token and
layer: the z, x, B, C and dt projections and the output projection, the
depthwise convolution, and the SSD mixer as the program computes it in
chunks of ``ssd_chunk`` tokens: C.B within the chunk, the masked product
with dt x over the chunk, the chunk's state and the state read-out. The LM
head runs on the S - 1 positions that predict a token.
"""


def train_flops_per_token(cfg: dict, seq_len: int) -> float:
    d, n = cfg["d_model"], cfg["ssm_state"]
    d_inner = cfg["ssm_expand"] * d
    heads, p = d_inner // cfg["ssm_headdim"], cfg["ssm_headdim"]
    chunk = min(cfg["ssd_chunk"], seq_len)
    proj = 2 * (2 * d * d_inner + 2 * d * n + d * heads + d_inner * d)
    conv = 2 * cfg["ssm_conv"] * (d_inner + 2 * n)
    mixer = 2 * chunk * n + 2 * chunk * heads * p + 2 * 2 * heads * p * n
    per_seq = (cfg["n_layers"] * seq_len * (proj + conv + mixer)
               + (seq_len - 1) * 2 * d * cfg["vocab_size"])
    return 3.0 * per_seq / seq_len
