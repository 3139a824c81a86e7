"""Mean host time, in milliseconds, of the step function's call until
it returns (not until the device finishes), over the window."""


def read(run):
    xs = run['dispatch_ms']
    return sum(xs) / len(xs) if xs else None
