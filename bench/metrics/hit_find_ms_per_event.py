"""Device milliseconds per event in the hit_find stage.

The device time of the streaming program's ops whose JAX source path holds
the stage's named scope ``hit_find`` (the threshold scan over ticks and the
compaction of the hits), over the events the window completed. Recon cells
only."""
from bench import scopes

LAYER = "hit finding (core/hitfind.py scan)"
UNIT = "ms/event"
MOVES = "events_per_s"


def read(rec):
    return scopes.ms_per_event(rec, scopes.scope_ns(rec, "hit_find"))
