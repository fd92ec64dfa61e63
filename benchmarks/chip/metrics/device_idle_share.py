"""Device (TPU v5e): share of the traced window in which no XLA operation
ran on the device, in percent (1 - union of op intervals / window)."""


def read(r):
    t = r.trace
    if t is None or not t["devices"] or t["window_s"] <= 0:
        return None
    return 100.0 * (1.0 - t["busy_s"] / t["window_s"])
