"""Device milliseconds per event in the convolve stage.

The device time of the streaming program's ops whose JAX source path holds
the stage's named scope ``convolve`` (the response convolution's forward
and inverse 2-D transforms), over the events the window completed."""
from bench import scopes

LAYER = "frequency-domain passes (core/fft_conv.py, core/noise.py, core/deconvolve.py)"
UNIT = "ms/event"
MOVES = "events_per_s"


def read(rec):
    return scopes.ms_per_event(rec, scopes.scope_ns(rec, "convolve"))
