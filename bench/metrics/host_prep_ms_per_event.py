"""Host milliseconds per event spent preparing batches.

For each dispatch in the window, the time from the end of the last
benchmark span before it (the copy of the batch before last, or the
previous dispatch) to the dispatch: generation, screening, packing and
staging of that batch. Summed over the window, divided by its events."""
LAYER = "host stream (launch/sim.py stream_simulate)"
UNIT = "ms/event"
MOVES = "events_per_s"


def read(rec):
    prep = rec.host_prep()
    if not prep or rec.events <= 0:
        return None
    return 1e-6 * sum(e - s for s, e in prep) / rec.events
