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
plain dict), never the program's objects, so no later change to the
program can move these counts.
"""

from __future__ import annotations

BF16 = 2
F32 = 4


def _d_inner(m: dict) -> int:
    return m["ssm_expand"] * m["d_model"]


def ssm_heads(m: dict) -> int:
    return _d_inner(m) // m["ssm_headdim"]


def n_super(m: dict) -> int:
    """Shared-attention invocations of a hybrid; its attention layers."""
    return m["n_layers"] // m["shared_attn_period"]


def attention_layers(m: dict) -> int:
    if m["family"] == "hybrid":
        return n_super(m)
    return m["n_layers"]


def matmul_weights_per_token(m: dict) -> int:
    """Weights one token multiplies through once, embeddings and the
    unembedding excluded (logits are counted apart)."""
    d, f = m["d_model"], m["d_ff"]
    H, K, hd = m["n_heads"], m["n_kv_heads"], m["head_dim"]
    if m["family"] == "dense":
        attn = d * H * hd + 2 * d * K * hd + H * hd * d
        mlp = (3 if m["mlp"] in ("swiglu", "geglu") else 2) * d * f
        return m["n_layers"] * (attn + mlp)
    if m["family"] == "hybrid":
        din, N = _d_inner(m), m["ssm_state"]
        Hs = ssm_heads(m)
        mamba = d * (2 * din + 2 * N + Hs) + din * d
        d2, r = 2 * d, m["shared_attn_lora"]
        shared = (d2 * H * hd + 2 * d2 * K * hd + H * hd * d   # q k v o
                  + d2 * r + r * H * hd                         # LoRA on q
                  + 2 * d2 * f + f * d)                         # SwiGLU MLP
        return m["n_layers"] * mamba + n_super(m) * shared
    raise ValueError(f"no counts for family {m['family']!r}")


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
    if m["family"] not in ("ssm", "hybrid"):
        return 0.0
    Q = ssd_chunk(m, rows) if rows > 1 else 1
    P, N = m["ssm_headdim"], m["ssm_state"]
    per = 2.0 * (Q * N + Q * P + 2.0 * P * N)
    return per * ssm_heads(m) * rows * m["n_layers"]


def ssd_bytes(m: dict, rows: int) -> float:
    """Bytes one ``rows``-row SSD kernel call must move, every Mamba layer:
    x, dt, B, C in and y out (bf16), the carried and final state (f32)."""
    Hs, P, N = ssm_heads(m), m["ssm_headdim"], m["ssm_state"]
    per_layer = (BF16 * rows * (2 * Hs * P + Hs + 2 * N)
                 + F32 * 2 * Hs * P * N)
    return per_layer * m["n_layers"]


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
