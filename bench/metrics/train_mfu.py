"""train_mfu: the fine-tuning step's share of the chip's peak, in the traced
window: the algorithmic operations per token of the configuration's work
module (``"work"``, e.g. bench/work/spt.py) times the tokens trained in the
window, over the window's length times the devices' bf16 peak."""
from bench import common


def read(r):
    cell, run, tr = r["cell"], r["run"], r["trace"]
    per_token = common.work_of(cell).train_flops_per_token(
        cell.config["model"], cell.traffic["seq_len"],
        cell.config["source_config"]["vocab_size"])
    flops = per_token * run["work"]["tokens"]
    return 100.0 * flops / (tr["window_s"] * tr["devices"]
                            * r["peaks"]["flops_bf16"])
