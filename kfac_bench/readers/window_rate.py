"""Samples (tokens, images) trained in the window over the wall time
of the whole window, the window closed by waiting for its last step."""


def read(run):
    if not run['steps']:
        return None
    return run['steps'] * run['samples_per_step'] / run['window_s']
