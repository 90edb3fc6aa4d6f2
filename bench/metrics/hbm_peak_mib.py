"""Peak device memory in use (``peak_bytes_in_use`` of the fullest chip),
read after the window and before the reference runs, in MiB."""


def read(run):
    return run.peak_bytes / 2**20 if run.peak_bytes else None
