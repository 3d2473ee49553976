"""Peak device memory in use (``peak_bytes_in_use`` of the fullest chip),
in GB, read once the window's ``peak_after_answers``-th answer is in (the
traffic file sets that count): set-up and a fixed number of products."""


def read(run):
    return run.peak_bytes / 1e9 if run.peak_bytes else None
