"""Device milliseconds per event in the noise stage.

The device time of the streaming program's ops whose JAX source path holds
the stage's named scope ``noise`` (the frequency-shaped normals and their
inverse transform), over the events the window completed."""
from bench import scopes

LAYER = "frequency-domain passes (core/fft_conv.py, core/noise.py, core/deconvolve.py)"
UNIT = "ms/event"
MOVES = "events_per_s"


def read(rec):
    return scopes.ms_per_event(rec, scopes.scope_ns(rec, "noise"))
