"""Mean time of one aggregation: the node's ``aggregate`` telemetry span
(the strategy's weighted mean over the stacked flats and the unflatten)."""


def read(record):
    n, seconds = record.get("phases", {}).get("aggregate", (0, 0.0))
    if record.get("kind") != "train" or not n:
        return None
    return {"value": 1e3 * seconds / n, "unit": "ms"}
