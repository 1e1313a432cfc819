"""routed_ffn_fwd_roofline.train: the grouped routed-FFN kernel's share of
its roofline in the fine-tuning step. Per call, the least time of its
algorithmic work (G' of G blocks with LoRA per token; every group's
weights, tokens in and out once) by bench/work/spt.py; over the summed
device time of its Pallas calls in the trace. Nothing is read where no
such call ran."""
from bench import trace
from bench.work import spt


def patterns(b: int, m: dict):
    """The kernel by its type in the trace: a Pallas call (custom-call
    tpu_custom_call) whose output is (batch, groups, capacity, d_model)
    bf16."""
    return [rf"= bf16\[{b},{m['groups']},\d+,{m['d_model']}\]\S* "
            r"custom-call tpu_custom_call"]


def read(r):
    cell = r["cell"]
    t, m = cell.traffic, cell.config["model"]
    seconds, calls = trace.kernel(r["trace"], patterns(t["batch"], m))
    if not calls:
        return None
    per_call = spt.least_seconds(
        spt.routed_ffn_fwd(m, t["batch"], t["seq_len"]), r["peaks"])
    return 100.0 * per_call * calls / seconds
