"""Encoding time per round: the program's ``encode`` telemetry spans (the
transport turning the node's own update into a blob, inside ``push``),
summed and divided by the rounds of the window."""


def read(record):
    n, seconds = record.get("phases", {}).get("encode", (0, 0.0))
    if record.get("kind") != "train" or not n or not record.get("rounds"):
        return None
    return {"value": 1e3 * seconds / record["rounds"], "unit": "ms"}
