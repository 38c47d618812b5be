"""1 - (union of device operation intervals in the window / window), from
the profiler trace (bench/xplane.py)."""


def read(record):
    t = record.trace
    if t is None or t.window_ns <= 0:
        return None
    return 1.0 - t.busy_ns / t.window_ns
