"""Operations and bytes the algorithm needs, from shapes alone.

``s`` is a model spec (``weights.spec_of``).  A FLOP is a multiply or an
add: a (m, k) x (k, n) product is 2·m·k·n.  Attention is causal: a query at
position i attends to i + 1 keys.  Pads, inactive batch rows and the
context bucket are not work the algorithm needs, so nothing here counts
them.
"""
from __future__ import annotations

BF16 = 2


def layer_matmul_params(s: dict) -> int:
    """Weights one token multiplies through in one layer."""
    d, hd = s["d"], s["head_dim"]
    qd, kvd = s["heads"] * hd, s["kv_heads"] * hd
    return d * qd + 2 * d * kvd + qd * d + 3 * d * s["ff"]


def head_flops(s: dict) -> int:
    return 2 * s["d"] * s["vocab"]


def token_flops(s: dict, keys: int) -> int:
    """One token through every layer, attending to ``keys`` positions
    (itself included), without the output head."""
    attn = 4 * s["heads"] * s["head_dim"] * keys     # q·k and p·v
    return s["layers"] * (2 * layer_matmul_params(s) + attn)


def prefill_flops(s: dict, n: int) -> int:
    """A prompt of ``n`` tokens: every token through the layers, causal
    attention, and the head at the last position only."""
    lin = s["layers"] * 2 * layer_matmul_params(s) * n
    attn = s["layers"] * 2 * s["heads"] * s["head_dim"] * n * (n + 1)
    return lin + attn + head_flops(s)


def decode_flops(s: dict, keys: int) -> int:
    """One decoded token whose query attends to ``keys`` positions."""
    return token_flops(s, keys) + head_flops(s)


def weight_bytes_per_step(s: dict) -> int:
    """Weights a decode step has to read once: every layer, the final norm
    and the output head (the embedding table itself when tied; an untied
    table is only gathered, one row per token)."""
    d, hd, n = s["d"], s["head_dim"], s["layers"]
    qd, kvd = s["heads"] * hd, s["kv_heads"] * hd
    per_layer = layer_matmul_params(s) * BF16 + 2 * d * 4
    if s["qkv_bias"]:
        per_layer += (qd + 2 * kvd) * BF16
    if s["qk_norm"]:
        per_layer += 2 * hd * 4
    return n * per_layer + d * 4 + s["vocab"] * d * BF16


def kv_bytes(s: dict, keys: int) -> int:
    """K and V of ``keys`` positions in every layer, as cached (bf16)."""
    return 2 * s["layers"] * s["kv_heads"] * s["head_dim"] * BF16 * keys
