"""Seconds of set-up the program spent importing: ``exec_module`` of its own
modules and of what they import first (``scipy``, ``jax.experimental.pallas``),
from the program's ``import`` records; the caller's ``import jax`` is not in
it."""

from cellbench import setup_stages


def read(run):
    return setup_stages.seconds_before_window(run, ("import",))
