"""The whole round's share of the chip's peak: the model operations of the
train steps that ran in the traced window (``bench/costs.train_flops`` per
step) over the window's length times the peak bf16 rate."""


def read(record):
    summary = record.get("trace")
    if record.get("kind") != "train" or summary is None:
        return None
    n, _ = summary.module("train_step")
    if n == 0 or summary.window_s <= 0:
        return None
    flops = n * record["flops_per_step"]
    return {"value": 100.0 * flops / (summary.window_s * record["peaks"]["bf16_flops"]),
            "unit": "%"}
