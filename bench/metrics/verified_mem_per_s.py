"""Memories of the window's jobs, all completed, over the window's
seconds on the host clock: the sum of the jobs' spans, each from the call
of ``fuzz_program`` to its return.  One job is in flight at a time; what
lies between spans is the benchmark drawing the next job's memories."""


def read(record):
    if not record.jobs or record.window_s <= 0:
        return None
    return record.memories / record.window_s
