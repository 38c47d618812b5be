"""95th percentile, by nearest rank, of the time from a job's start to its
verdict (the return of ``fuzz_program``), over every job of the window,
on the host clock."""
import math


def read(record):
    times = sorted(job.seconds for job in record.jobs)
    if not times:
        return None
    return 1000.0 * times[math.ceil(0.95 * len(times)) - 1]
