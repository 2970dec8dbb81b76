"""peak_hbm_gib: peak device memory of the fullest chip, in GiB.

`memory_stats()["peak_bytes_in_use"]`, read after the window and before the
reference runs: it bounds the largest grid a user can advance.
"""


def read(run):
    """Peak bytes in use on the fullest device, in GiB."""
    if run.peak_bytes is None:
        return None
    return run.peak_bytes / 2 ** 30
