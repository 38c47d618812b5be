"""Simulated PE-cycles of the traced part of the window (rows x caller's
memories x PEs per chunk, padding rows excluded) over the device's busy
nanoseconds in it, from the profiler trace.  The same work whichever
cycle step runs it."""


def read(record):
    t = record.trace
    if t is None or t.busy_ns <= 0 or not record.pe_cycles:
        return None
    return record.pe_cycles / t.busy_ns
