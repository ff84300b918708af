"""Tokens of every train step completed in the window, over the window's
wall time (the window ends with the chunk that passes ``--seconds``)."""


def read(record):
    if record["kind"] != "train" or record["window_s"] <= 0:
        return None
    return record["tokens"] / record["window_s"]
