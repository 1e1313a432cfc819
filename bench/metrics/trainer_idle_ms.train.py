"""trainer_idle_ms.train: device 0's idle time per fine-tuning step inside
the program's step spans ("spt.train", opened by ``Trainer.run``), in ms:
the time the host keeps the chip waiting between steps (fetching the
step's metrics, the hook, the next batch, its placement and the next
dispatch). Nothing is read where the trace holds no step span."""
from bench import spans


def read(r):
    events = spans.of_cell(r["cell"])
    ran = spans.steps(events) if events else []
    if not ran:
        return None
    return spans.idle_in(events, ran) / len(ran) * 1e-6
