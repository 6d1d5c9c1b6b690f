"""Device time of one train step: the traced window's executions of the
jitted ``train_step`` program, their device seconds over their count."""


def read(record):
    summary = record.get("trace")
    if record.get("kind") != "train" or summary is None:
        return None
    n, seconds = summary.module("train_step")
    if n == 0:
        return None
    return {"value": 1e3 * seconds / n, "unit": "ms"}
