"""Device time of one solve: the device's busy time in the traced window ÷
the solves completed in it."""


def read(run):
    per_op = run.device_seconds_per_operation()
    return None if per_op is None else per_op * 1e3
