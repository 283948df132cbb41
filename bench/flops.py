"""Operations and bytes of the work the program does, as functions of the
true shapes of each call: unpadded token counts and the real head width,
never the tiles ``kernels/ops.py`` pads to. A kernel's roofline share then
reads the same work whatever implements it, and a change that drops the
padding shows as a higher share, not as a new count.

Bytes are the call's necessary traffic to and from HBM: every input read
once and every output written once. Operations count the multiply-adds of
the matrix products (2 per product term); softmax and elementwise work is
left out, as it runs beside the matrix unit.
"""
from __future__ import annotations

BF16, F32, I32 = 2, 4, 4


def synapse_attention(B: int, H: int, Hkv: int, T: int, D: int, itemsize: int = BF16):
    """One fused attend of B lanes over T slots: q [B,H,D], k/v [B,T,Hkv,D],
    a [B,T] mask in; out [B,H,D] and per-slot mass [B,T] f32 out."""
    flops = 4 * B * H * T * D  # q·k and p·v
    nbytes = (B * H * D * itemsize          # q
              + 2 * B * T * Hkv * D * itemsize  # k, v
              + B * T * 1                       # mask
              + B * H * D * itemsize          # out
              + B * T * F32)                  # mass
    return flops, nbytes


def landmark_score(B: int, H: int, Hkv: int, T: int, D: int, itemsize: int = BF16):
    """One density sweep (no landmarks): q [B,H,D], keys [B,T,Hkv,D] in;
    per-head logits [B,H,T] f32 out."""
    flops = 2 * B * H * T * D
    nbytes = B * H * D * itemsize + B * T * Hkv * D * itemsize + B * H * T * F32
    return flops, nbytes


def decode_token_flops(m: dict, slots: float) -> float:
    """Model operations to decode one token that attends over ``slots``
    key/value slots: the dense projections and MLP of every layer, the
    head, and attention over the slots actually held."""
    d, H, Hkv, D, ff = m["d_model"], m["n_heads"], m["n_kv_heads"], m["d_head"], m["d_ff"]
    per_layer = d * H * D + 2 * d * Hkv * D + H * D * d + 3 * d * ff
    dense = 2 * (m["n_layers"] * per_layer + d * m["vocab_size"])
    return dense + 4 * m["n_layers"] * H * D * slots


def roofline_share(flops: float, nbytes: float, seconds: float, peaks: dict) -> float:
    """Least time the chip could take (the larger of operations over peak
    FLOP/s and bytes over HBM bandwidth), as a percentage of ``seconds``."""
    least = max(flops / peaks["flops_bf16"], nbytes / peaks["hbm_bytes_per_s"])
    return 100.0 * least / seconds
