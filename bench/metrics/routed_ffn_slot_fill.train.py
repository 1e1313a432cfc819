"""routed_ffn_slot_fill.train: the share of the grouped routed-FFN
kernel's (B, G, C) slots that hold a routed (token, group) pair, over the
window's steps: 100 x the kept pairs over the slots, summed from the
program's per-step counter event ("spt.train.counters", opened by
``Trainer.run``). Capacity above the mean load and dropped pairs both
lower it. Nothing is read where the trace holds no counter event."""
from bench import spans


def read(r):
    events = spans.of_cell(r["cell"])
    counts = spans.counters(events) if events else []
    if not counts:
        return None
    return 100.0 * (sum(c["routed_kept"] for c in counts)
                    / sum(c["routed_slots"] for c in counts))
