"""Device milliseconds per event in the frequency-domain passes.

Interim, by op kind: the device time of the trace's ops whose JAX source
path names an FFT (convolution, noise, deconvolution), over the events
the window completed. Stage scopes in the program (a ``jax.named_scope``
per stage) will replace the op-kind match."""
import re

LAYER = "frequency-domain passes (core/fft_conv.py, core/noise.py, core/deconvolve.py)"
UNIT = "ms/event"
MOVES = "events_per_s"
PATTERN = re.compile(r"jit\(fft\)|/i?r?fft")


def read(rec):
    if not rec.ops or rec.events <= 0:
        return None
    s = rec.op_seconds(lambda name, kind: bool(PATTERN.search(kind)))
    return 1e3 * s / rec.events if s > 0 else None
