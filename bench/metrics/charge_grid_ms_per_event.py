"""Device milliseconds per event in the charge_grid stage.

The device time of the streaming program's ops whose JAX source path holds
the stage's named scope ``charge_grid`` (patches, fluctuation and the
scatter-add loop with the ops of its body), over the events the window
completed."""
from bench import scopes

LAYER = "charge grid (core/pipeline.py unfused chain, core/scatter.py)"
UNIT = "ms/event"
MOVES = "events_per_s"


def read(rec):
    return scopes.ms_per_event(rec, scopes.scope_ns(rec, "charge_grid"))
