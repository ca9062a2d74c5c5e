"""The program's stage scopes in a traced window.

The simulator runs each stage of its graph under ``jax.named_scope``
(``core/stages.py`` ``SimGraph.run_state``), so every op of the compiled
program carries its stage in its JAX source path
(``jit(run)/vmap(charge_grid)/scatter-add``), which ``tracereduce``
keeps as the op's kind. The stage readers use the helpers below; on a
program without the scopes each of them finds nothing and reports
nothing.
"""
from __future__ import annotations

import re
from typing import Optional


def scope_pattern(stage: str) -> re.Pattern:
    """A stage's named scope as a whole element of an op's source path:
    ``/stage/``, ``(stage)`` or ``(stage/`` (``vmap(noise)`` and
    ``vmap(vmap(noise))`` match ``noise``; ``jit(simulate_noise)`` does
    not)."""
    return re.compile(r"(?:^|[/(])" + re.escape(stage) + r"(?:[/)]|$)")


def scope_ns(rec, stage: str) -> Optional[int]:
    """Device nanoseconds (a union, so a matched loop and the ops of its
    body count once) of the ops whose source path holds the stage's scope;
    None when no op does."""
    pat = scope_pattern(stage)
    s = rec.op_seconds(lambda name, kind: bool(pat.search(kind)))
    return round(s * 1e9) if s > 0 else None


def ms_per_event(rec, ns: Optional[int]) -> Optional[float]:
    if ns is None or rec.events <= 0:
        return None
    return 1e-6 * ns / rec.events
