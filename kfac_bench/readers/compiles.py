"""Programs JAX built (compiled, or loaded from the cache) plus step
variants traced inside the window. Anything but 0 is a finding."""


def read(run):
    return float(run['builds_in_window'] + run['traces_in_window'])
