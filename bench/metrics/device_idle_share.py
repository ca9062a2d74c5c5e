"""Share of the traced window in which no operation ran on the device.

1 - (union of the device's op intervals) / (window), averaged over the
chips traced. The host stream (``stream_simulate``) leaves the device idle
while it generates, screens, packs and stages the next batch, and while it
copies results back."""
LAYER = "host stream (launch/sim.py stream_simulate)"
UNIT = "%"
MOVES = "events_per_s"


def read(rec):
    if not rec.ops or rec.window_s() <= 0:
        return None
    return 100.0 * (1.0 - rec.busy_s() / rec.window_s())
