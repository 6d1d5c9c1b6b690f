"""Device time of the expert layers per train step: the traced window's
operations under the program's ``moe_route``, ``moe_experts`` and
``moe_combine`` scopes (the union of their intervals, forward and backward),
over the window's executions of the ``train_step`` program."""


def read(record):
    seconds = record.get("scope_seconds", {}).get("moe")
    summary = record.get("trace")
    if record.get("kind") != "train" or not seconds or summary is None:
        return None
    n, _ = summary.module("train_step")
    if n == 0:
        return None
    return {"value": 1e3 * seconds / n, "unit": "ms"}
