"""Host milliseconds spent in the harvest layer's spans (bench/seam.py)
per 1,000 memories of the window."""


def read(record):
    seconds = record.layer_s.get("harvest")
    if seconds is None or not record.memories:
        return None
    return 1000.0 * seconds / (record.memories / 1000.0)
