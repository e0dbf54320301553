"""Operations and bytes the served model needs, from its configuration.

The benchmark's own yardstick for the roofline and MFU metrics, copied
from the program's ``hw/flops.py`` (``_attention_flops_fwd``,
``_ssd_flops_fwd``, ``_cache_bytes``) and made exact where that module
approximates:

  * matrix-multiply weights are counted as they are APPLIED per token, so
    a shared block that runs n times counts n times (``hw/flops.py`` counts
    declared parameters once);
  * causal attention over a prefill chunk at offset ``o`` covers
    S*o + S*(S+1)/2 query-key pairs, not a halved square;
  * the SSD chunk is the one the program picks for the call's length.

Every function takes the ``model`` section of a configuration file (a
plain dict, or ``reference.model_view``), never the program's objects, so
no later change to the program can move these counts.  What depends on
the model's structure -- the weights a token multiplies through, how many
layers attend and how many run the SSD scan, the width of B and C, the
weights under the ``ssm`` scope -- comes from the model's kind module
(``chipbench/reference/<kind>.py``); the arithmetic here is the same for
every kind.
"""

from __future__ import annotations

from chipbench import reference

BF16 = 2
F32 = 4


def _d_inner(m: dict) -> int:
    return m["ssm_expand"] * m["d_model"]


def ssm_heads(m: dict) -> int:
    return _d_inner(m) // m["ssm_headdim"]


def attention_layers(m: dict) -> int:
    return reference.kind(m).attention_layers(m)


def ssm_layers(m: dict) -> int:
    return reference.kind(m).ssm_layers(m)


def matmul_weights_per_token(m: dict) -> int:
    """Weights one token multiplies through once, embeddings and the
    unembedding excluded (logits are counted apart)."""
    return reference.kind(m).matmul_weights_per_token(m)


def ssm_scope_weights(m: dict) -> int:
    """Matmul weights the program runs under its ``ssm`` scope."""
    return reference.kind(m).ssm_scope_weights(m)


def attention_pairs(offset: int, rows: int) -> int:
    """Causal query-key pairs of ``rows`` new rows after ``offset`` rows."""
    return rows * offset + rows * (rows + 1) // 2


def attention_flops(m: dict, pairs: int) -> float:
    """QK^T and PV over ``pairs`` query-key pairs, every attention layer."""
    return 4.0 * pairs * m["n_heads"] * m["head_dim"] * attention_layers(m)


def ssd_chunk(m: dict, rows: int) -> int:
    """The SSD chunk the program uses for a ``rows``-row prefill call."""
    q = min(m["ssm_chunk"], rows)
    while rows % q:
        q -= 1
    return q


def ssd_flops(m: dict, rows: int) -> float:
    """SSD scan of one ``rows``-row call, every Mamba layer: per token and
    head 2 * (QN + QP + 2PN) (``hw/flops._ssd_flops_fwd``)."""
    layers = ssm_layers(m)
    if not layers:
        return 0.0
    Q = ssd_chunk(m, rows) if rows > 1 else 1
    P, N = m["ssm_headdim"], m["ssm_state"]
    per = 2.0 * (Q * N + Q * P + 2.0 * P * N)
    return per * ssm_heads(m) * rows * layers


def ssd_bytes(m: dict, rows: int) -> float:
    """Bytes one ``rows``-row SSD kernel call must move, every Mamba layer:
    x, dt, B, C in and y out (bf16), the carried and final state (f32)."""
    layers = ssm_layers(m)
    if not layers:
        return 0.0
    Hs, P, N = ssm_heads(m), m["ssm_headdim"], m["ssm_state"]
    bc = reference.kind(m).bc_width(m)
    per_layer = (BF16 * rows * (2 * Hs * P + Hs + bc)
                 + F32 * 2 * Hs * P * N)
    return per_layer * layers


def logits_flops(m: dict, positions: int) -> float:
    return 2.0 * positions * m["d_model"] * m["vocab"]


def prefill_flops(m: dict, offset: int, rows: int) -> float:
    """One prefill chunk of ``rows`` rows after ``offset`` cached rows."""
    return (2.0 * matmul_weights_per_token(m) * rows
            + attention_flops(m, attention_pairs(offset, rows))
            + ssd_flops(m, rows))


def decode_flops(m: dict, kv_len: int) -> float:
    """One decoded token whose query sees ``kv_len`` rows, with its logits."""
    return (2.0 * matmul_weights_per_token(m)
            + attention_flops(m, kv_len)
            + ssd_flops(m, 1) + logits_flops(m, 1))


def decode_attention_bytes(m: dict, kv_len: int) -> float:
    """Bytes the paged decode kernel needs for one slot's one token, every
    attention layer: K and V rows up to ``kv_len``, the query and the
    output (bf16)."""
    K, H, hd = m["n_kv_heads"], m["n_heads"], m["head_dim"]
    per_layer = BF16 * (2 * kv_len * K * hd + 2 * H * hd)
    return per_layer * attention_layers(m)


def weight_bytes(m: dict) -> float:
    """bf16 bytes of the weights a decode iteration reads (embedding rows
    aside), the unembedding included."""
    return BF16 * (matmul_weights_per_token(m) + m["d_model"] * m["vocab"])


def chunk_plan(length: int, max_chunk: int) -> list[int]:
    """Power-of-two prefill chunks, largest first -- the engine's split of
    a prompt (``serving/scheduler.chunk_plan``), copied so that the counts
    above do not depend on the program."""
    plan, c, rem = [], max_chunk, length
    while rem:
        while c > rem:
            c //= 2
        plan.append(c)
        rem -= c
    return plan
