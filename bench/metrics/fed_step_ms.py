"""Mean wall time of one federation step: the benchmark's span around
``FederatedCallback.on_epoch_end`` (host params, push, pull, aggregate,
``set_params``), per round of the window."""


def read(record):
    n, seconds = record.get("phases", {}).get("fed_step", (0, 0.0))
    if record.get("kind") != "train" or not n:
        return None
    return {"value": 1e3 * seconds / n, "unit": "ms"}
