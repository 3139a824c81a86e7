"""Share of the traced window in which no operation ran on the
device, in percent: 1 - union of device-op intervals / window."""


def read(run):
    if run['reduced'] is None or not run['reduced']['events']:
        return None
    return 100.0 * (1.0 - run['reduced']['busy_s'] / run['window_s'])
