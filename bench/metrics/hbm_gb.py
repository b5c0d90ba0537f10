"""hbm_gb: device bytes in use (fullest chip) once the window's requests
are answered and before the check allocates anything, in GB (1e9)."""


def read(run):
    return run.bytes_in_use / 1e9 if run.bytes_in_use else None
