"""Host-to-device copy time per round: the program's ``set_params``
telemetry spans (``Trainer.set_params`` placing the aggregate back on the
device, until it is ready), summed and divided by the rounds of the
window."""


def read(record):
    n, seconds = record.get("phases", {}).get("set_params", (0, 0.0))
    if record.get("kind") != "train" or not n or not record.get("rounds"):
        return None
    return {"value": 1e3 * seconds / record["rounds"], "unit": "ms"}
