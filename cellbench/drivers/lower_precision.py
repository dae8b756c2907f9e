"""What the dense drivers' ``controls`` share: the program's own kernel
regimes below the shipping one, put in the place of a driver's ``step``.
They exist on the TPU only (off it the XLA path serves either way)."""

from __future__ import annotations


def program_at(step, state, precision: str):
    """``step(state, i)`` under the fused kernel's ``precision`` regime."""
    from libskylark_tpu.sketch import params as sketch_params

    def run(i):
        before = sketch_params.get_pallas_precision()
        sketch_params.set_pallas_precision(precision)
        try:
            return step(state, i)
        finally:
            sketch_params.set_pallas_precision(before)
    return run
