"""Peak device memory in use after the window, on the fullest chip, GiB."""


def read(run):
    return run.peak_bytes / 2 ** 30
