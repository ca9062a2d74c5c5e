"""Device milliseconds per event spent on the two induction planes (U and V).

The device time of the streaming program's ops whose JAX source path holds
the plane-kind scope ``induction`` that the multi-plane convolve, noise,
deconvolve and hit_find stages put around each plane's work
(``vmap(convolve)/induction/...``), over the events the window completed.
A program without the scope (one plane, or planes of one wire count)
reports nothing."""
from bench import scopes

LAYER = "per-plane readout (core/stages.py multi-plane stages)"
UNIT = "ms/event"
MOVES = "events_per_s"


def read(rec):
    return scopes.ms_per_event(rec, scopes.scope_ns(rec, "induction"))
