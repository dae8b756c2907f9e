"""Device time of the ops inside one apply: the device's busy time in the
traced window ÷ the applies completed in it."""


def read(run):
    per_op = run.device_seconds_per_operation()
    return None if per_op is None else per_op * 1e3
