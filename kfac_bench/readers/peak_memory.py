"""``peak_bytes_in_use`` of the fullest device after the window, in
GiB."""


def read(run):
    if run['peak_bytes'] is None:
        return None
    return run['peak_bytes'] / 2 ** 30
