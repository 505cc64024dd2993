"""Operations and bytes that the algorithm REQUIRES, from shapes alone.

Kept with the benchmark so no PR that claims a gain can change the
yardstick. The program's own ``LlamaForCausalLM.flops_per_token`` counts
the embedding gather as a matmul and attention as non-causal (ROADMAP
A3); these do not.
"""
from __future__ import annotations


def matmul_params(m: dict, layers: int) -> int:
    """Weights that take part in a matmul (the embedding is a gather)."""
    h, d = m["hidden_size"], m["head_dim"]
    nh, nkv, ffn = (m["num_attention_heads"], m["num_key_value_heads"],
                    m["intermediate_size"])
    per_layer = h * (nh + 2 * nkv) * d + nh * d * h + 3 * h * ffn
    return layers * per_layer + h * m["vocab_size"]


def total_params(m: dict, layers: int) -> int:
    h = m["hidden_size"]
    return (matmul_params(m, layers) + m["vocab_size"] * h
            + (2 * layers + 1) * h)


def attn_flops_fwd(m: dict, layers: int, seq: int) -> float:
    """Forward FLOPs of causal attention over one sequence: QK^T and PV,
    2 FLOPs a multiply-add, only the s(s+1)/2 visible pairs."""
    nh, d = m["num_attention_heads"], m["head_dim"]
    return layers * 2 * 2 * nh * d * seq * (seq + 1) / 2


def train_flops_per_token(m: dict, layers: int, seq: int) -> float:
    """Forward + backward (2x forward) required FLOPs per trained token;
    no recompute, no gather."""
    fwd = 2 * matmul_params(m, layers) + attn_flops_fwd(m, layers, seq) / seq
    return 3 * fwd


def flash_fwd_bwd_flops(m: dict, seq: int, batch: int) -> float:
    """Required FLOPs of ONE layer's attention kernel calls, forward and
    backward, causal: fwd 2 matmuls, bwd 4 (dQ, dK, dV and the
    recomputed-by-necessity P is not counted) over visible pairs."""
    nh, d = m["num_attention_heads"], m["head_dim"]
    pairs = seq * (seq + 1) / 2
    return batch * nh * d * pairs * 2 * (2 + 4)


def flash_fwd_bwd_bytes(m: dict, seq: int, batch: int, itemsize=2) -> float:
    """Least HBM traffic of the same calls: fwd reads q, k, v and writes
    o; bwd reads q, k, v, o, do and writes dq, dk, dv."""
    nh, nkv, d = (m["num_attention_heads"], m["num_key_value_heads"],
                  m["head_dim"])
    q = batch * seq * nh * d * itemsize
    kv = batch * seq * nkv * d * itemsize
    return (2 * q + 2 * kv) + (4 * q + 4 * kv)


def weight_bytes(m: dict, layers: int, itemsize=2) -> int:
    """Bytes of weights a decode round has to read: every matmul weight
    once (embedding rows gathered are negligible)."""
    return matmul_params(m, layers) * itemsize


def kv_bytes_per_token(m: dict, layers: int, itemsize=2) -> int:
    return 2 * layers * m["num_key_value_heads"] * m["head_dim"] * itemsize


def decode_round_bytes(m: dict, layers: int, live_kv_tokens: float,
                       itemsize=2) -> float:
    """Least bytes one decode round moves: the weights once plus the live
    K/V of every running lane once (copied from the arithmetic of
    benchmarks/serving_bench.py ``kv_byte_model``; the original is listed
    in PERF.md for a later PR to delete)."""
    return (weight_bytes(m, layers, itemsize)
            + live_kv_tokens * kv_bytes_per_token(m, layers, itemsize))
