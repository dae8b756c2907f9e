"""Seconds of set-up inside jax's backend compile (the ``backend_compile``
records): XLA's and Mosaic's compile on a checkout's first run, the
persistent cache's load — which lies inside it — on every later one."""

from cellbench import setup_stages


def read(run):
    return setup_stages.seconds_before_window(run, ("backend_compile",))
