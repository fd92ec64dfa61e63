"""Remapping controller (core/remapping_controller.py): share of the
window's decode calls whose plan cycles layers from host memory (m > 0),
in percent."""


def read(r):
    calls = r.rec.decode_calls
    if not calls:
        return None
    return 100.0 * sum(1 for c in calls if c["m"] > 0) / len(calls)
