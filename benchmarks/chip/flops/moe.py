"""Model FLOPs of one training token of the sparse-expert decoder LM, as
one chip's expert share computes it.

Counted as matrix-multiply operations (2 per multiply-add) of the forward
pass, times 3 for forward plus backward; nothing recomputed is counted.
Per sequence of S tokens: the four attention projections, the router (d x
router_experts) and, on every token, the expected number of routed slots
that land on a held expert, experts_per_token * n_experts /
router_experts, each a SwiGLU expert of three d x d_ff matrices; the
score and value products over all S keys of every query (the causal half
is computed and masked, as in the PaLM count); and the tied head on the S
- 1 positions that predict a token.
"""


def held_slots_per_token(cfg: dict) -> float:
    return cfg["experts_per_token"] * cfg["n_experts"] / cfg["router_experts"]


def train_flops_per_token(cfg: dict, seq_len: int) -> float:
    d, hd = cfg["d_model"], cfg["head_dim"]
    hq, hk = cfg["n_heads"], cfg["n_kv_heads"]
    proj = d * hq * hd * 2 + d * hk * hd * 2          # q, o and k, v
    router = d * cfg["router_experts"]
    experts = held_slots_per_token(cfg) * 3 * d * cfg["d_ff"]
    attn = 2 * seq_len * hq * hd                     # q.k and p.v per token
    per_seq = (cfg["n_layers"] * seq_len
               * (2 * (proj + router + experts) + 2 * attn)
               + (seq_len - 1) * 2 * d * cfg["vocab_size"])
    return 3.0 * per_seq / seq_len
