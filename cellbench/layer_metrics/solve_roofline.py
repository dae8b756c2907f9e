"""Share of its roofline the solve reaches: the least time the chip could
take for the solve's passes over A (``counts/randsvd_passes.py`` over
``peaks.json``) ÷ the device time of one solve, in per cent."""


def read(run):
    per_op = run.device_seconds_per_operation()
    if per_op is None:
        return None
    least_s, _bound = run.least_time()
    return 100.0 * least_s / per_op
