"""Algorithmic operations and bytes of SPT's layers, from the configuration's
"model" block and the call's shapes.

Counted is the work SPT's definition requires, whichever path does it:
G'/G of the FFN groups, PQ code assignment and integer scoring of every
visible key, attention over the top-L kept keys only, the router, LoRA on
every projection, and the LM head. A multiply-add is two operations. In the
backward pass a frozen weight is differentiated for its input only (once
its forward work), a trainable one for input and weight (twice), attention
over kept keys twice, and codes, scores and selection not at all.
Recomputation is not counted. Bytes are the least a call must move: its
inputs and outputs once, in the dtype they are held in.
"""
from __future__ import annotations

from typing import Dict


def top_l(n: int, m: dict) -> int:
    l = max(m["min_l"], int(round(n * m["top_fraction"])))
    l = -(-l // m["pad_l_to"]) * m["pad_l_to"]
    return min(l, n)


def mean_kept(seq: int, m: dict) -> float:
    """Keys a causal query keeps, averaged over positions 0..seq-1."""
    l = top_l(seq, m)
    return sum(min(l, t + 1) for t in range(seq)) / seq


def attention_fwd_per_token(m: dict, seq: int) -> Dict[str, float]:
    """One layer's attention, per token of a causal sequence of ``seq``."""
    d, hq, hk, hd, r = (m["d_model"], m["heads"], m["kv_heads"],
                        m["head_dim"], m["lora_rank"])
    books = hd // m["code_dim"]
    proj = 2 * d * hq * hd * 2 + 2 * d * hk * hd * 2
    lora = 2 * r * ((d + hq * hd) * 2 + (d + hk * hd) * 2)
    codes = 2 * m["codewords"] * hd * (hq + hk)
    scores = hq * 2 * books * (seq + 1) / 2
    kept = hq * 4 * hd * mean_kept(seq, m)
    return {"proj": proj, "lora": lora, "codes": codes, "scores": scores,
            "kept": kept}


def ffn_fwd_per_token(m: dict) -> Dict[str, float]:
    """One layer's routed FFN, per token."""
    d, f, g, ga, r = (m["d_model"], m["d_ff"], m["groups"],
                      m["active_groups"], m["lora_rank"])
    fg = f // g
    mats = 3 if m["gated"] else 2
    blocks = ga * mats * 2 * d * fg
    ups = 2 if m["gated"] else 1
    lora = ups * (2 * d * r + ga * 2 * r * fg) + ga * 2 * fg * r + 2 * r * d
    return {"router": 2 * d * g, "blocks": blocks, "lora": lora}


def train_flops_per_token(m: dict, seq: int, vocab: int) -> float:
    """Forward and backward of one fine-tuning token, all layers."""
    a = attention_fwd_per_token(m, seq)
    f = ffn_fwd_per_token(m)
    fwd_layer = sum(a.values()) + sum(f.values())
    bwd_layer = (a["proj"] + f["blocks"]                 # frozen: input only
                 + 2 * (a["lora"] + f["lora"] + f["router"])
                 + 2 * a["kept"])
    head = 2 * m["d_model"] * vocab
    return m["layers"] * (fwd_layer + bwd_layer) + 2 * head


def sparse_attention_fwd(m: dict, batch: int, seq: int,
                         act_bytes: int = 2) -> Dict[str, float]:
    """One call of the fused sparse MHA forward (codes, scores, selection,
    kept attention) over (batch, seq): operations and bytes."""
    a = attention_fwd_per_token(m, seq)
    flops = batch * seq * (a["codes"] + a["scores"] + a["kept"])
    hd, hq, hk = m["head_dim"], m["heads"], m["kv_heads"]
    elems = batch * seq * hd * (2 * hq + 2 * hk)          # q, k, v in; out
    return {"flops": flops, "bytes": elems * act_bytes}


def routed_ffn_fwd(m: dict, batch: int, seq: int,
                   act_bytes: int = 2, w_bytes: int = 2) -> Dict[str, float]:
    """One call of the grouped routed-FFN forward over (batch, seq): every
    group's weights read once, tokens in and out once."""
    f = ffn_fwd_per_token(m)
    flops = batch * seq * (f["blocks"] + f["lora"])
    mats = 3 if m["gated"] else 2
    weights = mats * m["d_model"] * m["d_ff"] * w_bytes
    tokens = 2 * batch * seq * m["d_model"] * act_bytes
    return {"flops": flops, "bytes": weights + tokens}


def least_seconds(work: Dict[str, float], peaks: dict) -> float:
    """The least time the chip could take: compute or memory, the larger."""
    return max(work["flops"] / peaks["flops_bf16"],
               work["bytes"] / peaks["hbm_bytes_per_s"])
