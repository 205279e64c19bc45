"""Forward FLOPs of Mamba-2 (SSD) blocks: 2 x the projections' parameters
x tokens, plus the chunked SSD mixer's matrix products counted from their
shapes (chunk length Q, per chunk and head): C.B^T (Q x Q x N), the
masked product with X (Q x Q x P), the carried state's read-out
(Q x P x N) and its update (Q x N x P)."""


def backbone_forward(mc: dict, seq: int, tokens: int) -> float:
    d = mc["d_model"]
    H, P, N, G = mc["ssm_heads"], mc["ssm_head_dim"], mc["ssm_state"], mc["ssm_groups"]
    di = H * P
    proj = d * (2 * di + 2 * G * N + H) + di * d
    Q = min(mc["ssm_chunk"], seq)
    per_chunk = 2.0 * H * Q * (Q * N + Q * P + 2 * P * N)
    chunks = (tokens // seq) * (seq // Q)
    return mc["num_layers"] * (2.0 * proj * tokens + per_chunk * chunks)
