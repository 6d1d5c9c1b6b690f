"""Decoding time per round: the program's ``decode`` telemetry spans (the
transport turning the peers' blobs back into flat updates, inside ``pull``),
summed and divided by the rounds of the window."""


def read(record):
    n, seconds = record.get("phases", {}).get("decode", (0, 0.0))
    if record.get("kind") != "train" or not n or not record.get("rounds"):
        return None
    return {"value": 1e3 * seconds / record["rounds"], "unit": "ms"}
