"""device_idle_share.train: the share of the traced fine-tuning window in
which no operation ran on the device (1 - busy / window)."""


def read(r):
    tr = r["trace"]
    return 100.0 * (1.0 - tr["busy_s"] / tr["window_s"])
