"""Share of its roofline the apply reaches: the least time the chip could
take for one apply (``counts/dense_sketch.py`` over ``peaks.json``) ÷ the
device time of one apply, in per cent."""


def read(run):
    per_op = run.device_seconds_per_operation()
    if per_op is None:
        return None
    least_s, _bound = run.least_time()
    return 100.0 * least_s / per_op
