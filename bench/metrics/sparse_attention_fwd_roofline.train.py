"""sparse_attention_fwd_roofline.train: the fused sparse MHA forward's share
of its roofline in the fine-tuning step. Per layer call, the least time of
its algorithmic work (PQ codes, scores, kept attention; q, k, v in and the
output out once) by bench/work/spt.py; over the summed device time of its
Pallas calls in the trace: the PQ code kernels, the top-L threshold kernel
and the attention kernel. Nothing is read where no attention kernel ran."""
from bench import trace
from bench.work import spt


def patterns(b: int, s: int, m: dict):
    """The kernels by their type in the trace (the calls carry no names of
    their own): Pallas calls (custom-call tpu_custom_call) named pq_assign,
    and those whose output is the thresholds' (rows, seq, 2) int32 and the
    attention output's (rows, seq, head_dim) bf16."""
    rows, hd = b * m["heads"], m["head_dim"]
    kern = r"\S* custom-call tpu_custom_call"
    attn = rf"= bf16\[{rows},{s},{hd}\]{kern}"
    return {"attention": [attn],
            "family": [attn, rf"^pq_assign\S* = \S+{kern}",
                       rf"= s32\[{rows},{s},2\]{kern}"]}


def read(r):
    cell = r["cell"]
    t, m = cell.traffic, cell.config["model"]
    p = patterns(t["batch"], t["seq_len"], m)
    _, calls = trace.kernel(r["trace"], p["attention"])
    if not calls:
        return None
    seconds, _ = trace.kernel(r["trace"], p["family"])
    per_call = spt.least_seconds(
        spt.sparse_attention_fwd(m, t["batch"], t["seq_len"]), r["peaks"])
    return 100.0 * per_call * calls / seconds
