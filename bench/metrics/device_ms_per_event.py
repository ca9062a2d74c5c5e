"""Device-busy milliseconds per event completed in the traced window.

Union of the device's op intervals (averaged over the chips traced) over
the events the window completed: what the batched executor's program
costs per event, overlap with the host excluded."""
LAYER = "batched executor (core/batch.py, core/stages.py jit_executor)"
UNIT = "ms/event"
MOVES = "events_per_s"


def read(rec):
    if not rec.ops or rec.events <= 0:
        return None
    return 1e3 * rec.busy_s() / rec.events
