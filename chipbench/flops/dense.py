"""Forward FLOPs of a dense decoder's blocks: 2 x the blocks' matrix
parameters x tokens, plus attention scores and their product with V by
the PaLM convention (arXiv:2204.02311, App. B): 4 x heads x head_dim x
seq per token and layer."""


def backbone_forward(mc: dict, seq: int, tokens: int) -> float:
    d, f = mc["d_model"], mc["d_ff"]
    H, KV, hd = mc["num_heads"], mc["num_kv_heads"], mc["head_dim"]
    attn = d * H * hd + 2 * d * KV * hd + H * hd * d
    mlp = 3 * d * f if mc.get("mlp_type", "swiglu") in ("swiglu", "geglu") else 2 * d * f
    per_layer = 2.0 * (attn + mlp) * tokens + 4.0 * H * hd * seq * tokens
    return mc["num_layers"] * per_layer
