"""Share of the traced training window in which no operation ran on the
device: 1 − (union of busy intervals) / window."""


def read(record):
    summary = record.get("trace")
    if record.get("kind") != "train" or summary is None or summary.window_s <= 0:
        return None
    return {"value": 100.0 * (1.0 - summary.busy_s / summary.window_s), "unit": "%"}
