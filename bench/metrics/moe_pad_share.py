"""Share of the rows the expert layer's grouped matmul was given that no
token-choice filled: 1 − ``moe_rows`` / ``moe_rows_alloc``, the program's
routing counters averaged over the window's steps. (Dropless, the buffer
holds every choice that could reach a held expert; the share is the room
that routing left empty.)"""


def read(record):
    rows, alloc = record.get("moe_rows"), record.get("moe_rows_alloc")
    if record.get("kind") != "train" or not alloc or rows is None:
        return None
    return {"value": 100.0 * (1.0 - rows / alloc), "unit": "%"}
