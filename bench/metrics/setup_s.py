"""Process start to the start of the first timed job, on the host clock:
runtime start, mapping and the warm-up."""


def read(record):
    return record.setup_s
