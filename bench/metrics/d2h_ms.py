"""Device-to-host copy time per round: the program's ``host_params``
telemetry spans (``Trainer.host_params`` copying the parameters to numpy at
the start of each federation step), summed and divided by the rounds of the
window."""


def read(record):
    n, seconds = record.get("phases", {}).get("host_params", (0, 0.0))
    if record.get("kind") != "train" or not n or not record.get("rounds"):
        return None
    return {"value": 1e3 * seconds / record["rounds"], "unit": "ms"}
