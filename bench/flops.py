"""Operations and bytes that serving needs, from the configuration and the
context lengths alone, so the count is the same whatever implements it.

Counts are of the model's mathematics: two operations per multiply-add of
every matrix product that a token needs, the attention scores and the
weighted sum over the context it attends, and the output head once for
every token that is emitted.  Bytes are the least a step must move: every
weight once per step, the cached keys and values it reads, and the ones it
writes, at the served precision.
"""
from __future__ import annotations

from bench.weights import Model

BYTES = 2     # bfloat16 weights and cache


def layer_params(m: Model) -> int:
    """Parameters of the matrix products of one layer."""
    qd, kvd = m.heads * m.head_dim, m.kv_heads * m.head_dim
    attn = m.d * qd + 2 * m.d * kvd + qd * m.d
    mlp = (3 if m.act == "silu" else 2) * m.d * m.ff
    return attn + mlp


def weight_bytes(m: Model) -> int:
    """Weights a step reads: every layer's products and the output head
    (the embedding rows a step looks up are counted with the head when
    tied, and are a few rows otherwise)."""
    return BYTES * (m.layers * layer_params(m) + m.d * m.vocab)


def kv_bytes_per_position(m: Model) -> int:
    return BYTES * m.layers * 2 * m.kv_heads * m.head_dim


def token_flops(m: Model, ctx: int, head: bool) -> int:
    """One token at a position that attends ``ctx`` positions (itself
    included), with the head where the token is emitted."""
    attn = 4 * m.heads * m.head_dim * ctx
    return (m.layers * (2 * layer_params(m) + attn)
            + (2 * m.d * m.vocab if head else 0))


def decode_tick(m: Model, ctxs: list[int]) -> tuple[int, int]:
    """(operations, bytes) of one decode tick over the live slots, slot i
    attending ``ctxs[i]`` positions including the one it writes."""
    flops = sum(token_flops(m, c, head=True) for c in ctxs)
    kv = kv_bytes_per_position(m) * sum(ctxs)   # read, the new one written
    return flops, weight_bytes(m) + kv


def prefill_chunk(m: Model, start: int, n: int, last: bool
                  ) -> tuple[int, int]:
    """(operations, bytes) of ``n`` prompt tokens at positions
    [start, start + n) of one sequence; ``last`` when the chunk ends the
    prompt and so emits the first token."""
    flops = sum(token_flops(m, start + i + 1, head=False) for i in range(n))
    if last:
        flops += 2 * m.d * m.vocab
    kv = kv_bytes_per_position(m) * (start + n)   # context read, n written
    return flops, weight_bytes(m) + kv


def least_seconds(flops: int, nbytes: int, peak: dict) -> float:
    """The roofline: the longer of compute at peak and bytes at peak."""
    return max(flops / peak["bf16_flops_per_s"],
               nbytes / peak["hbm_bytes_per_s"])
