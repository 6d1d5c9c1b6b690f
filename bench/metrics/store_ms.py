"""Store and codec time per round: the node's ``push`` and ``pull``
telemetry spans (serialise and write its own update; hash the folder, read
and decode the peers'), summed and divided by the rounds of the window."""


def read(record):
    phases = record.get("phases", {})
    if record.get("kind") != "train" or "push" not in phases or not record.get("rounds"):
        return None
    total = phases["push"][1] + phases.get("pull", (0, 0.0))[1]
    return {"value": 1e3 * total / record["rounds"], "unit": "ms"}
