"""Device milliseconds per event in the deconvolve stage.

The device time of the streaming program's ops whose JAX source path holds
the stage's named scope ``deconvolve`` (the inverse filter's 2-D
transforms), over the events the window completed. Recon cells only."""
from bench import scopes

LAYER = "frequency-domain passes (core/fft_conv.py, core/noise.py, core/deconvolve.py)"
UNIT = "ms/event"
MOVES = "events_per_s"


def read(rec):
    return scopes.ms_per_event(rec, scopes.scope_ns(rec, "deconvolve"))
