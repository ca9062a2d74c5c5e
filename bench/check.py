"""The comparison that decides ``correct``.

After the window has closed, one batch of the window, drawn from the seed,
is compared event by event with the plain reference, stage by stage along
the chain the timed program ran, each stage fed by the reference's own
previous stage:

    grid_err      charge grid after fluctuation  ||prog - ref|| / ||ref||
    signal_err    after convolution and noise    ||prog - ref|| / ||ref||
    adc_mismatch  digitized counts               share of pixels that differ
    decon_err     deconvolved charge (recon)     ||prog - ref|| / ||ref||
    hit_mismatch  stored hits (recon)            share without a partner

Both sides are taken plane by plane, as lists with one array per plane, so
planes may differ in wire count. Norms and shares run over all planes of
an event; hits are partnered within their plane; each number is the worst
event of the batch. A number passes when it is at most its limit from the
cell's file (``bench/cells/<cell>.json``); ``PERF.md`` gives the readings
each limit was set from.

The reference is found through a module with three names: ``GENERATORS``
(the frozen event generators), ``reference_event`` (one event's outputs,
per plane, by the deployment's key schedule) and ``event_view`` (one event
of the program's batch, per plane). A configuration names its own module
under ``bench/references``; one that names none is checked by this module's
own three: ``depogen``'s generators, the plain reference's default
geometry (``reference.py``) with ``num_wires`` wires on every plane, and
the program's (E[, P], W, T) outputs.
"""
from __future__ import annotations

from concurrent.futures import ThreadPoolExecutor
from typing import TYPE_CHECKING, Dict, List

import numpy as np

from bench import depogen

if TYPE_CHECKING:  # SciPy's import takes seconds: the check imports it
    from bench import reference  # after the window, never in set-up

GENERATORS = depogen.GENERATORS

#: a program hit partners a reference hit on the same wire when its mean
#: tick and charge agree this closely
HIT_TICK_TOL = 0.5
HIT_CHARGE_TOL = 0.05


def sample_batch(seed: int, n_batches: int) -> int:
    """The batch of the window that is compared, drawn from the seed."""
    return int(np.random.default_rng(seed).integers(n_batches))


def reference_event(seed: int, event_id: int, sizes: dict, n_depos: int,
                    generator: str, recon: bool) -> reference.EventRef:
    """The default reference's outputs for one event of the stream
    ``seed``, per plane.

    The depos come from the frozen generator on the default device, as the
    program's stream draws them there; the normals from ``jax.random`` on
    the host CPU."""
    import jax
    import jax.numpy as jnp

    from bench import reference

    key = depogen.event_key(seed, event_id)
    phys = GENERATORS[generator].draw(key, n_depos, sizes)
    phys = {f: np.asarray(getattr(phys, f)) for f in phys._fields}
    cpu = jax.devices("cpu")[0]
    with jax.default_device(cpu):
        kf, kn = jax.random.split(depogen.event_key(seed, event_id))

    def draws(p: int):
        with jax.default_device(cpu):
            kfp, knp = ((kf, kn) if sizes["num_planes"] == 1 else
                        (jax.random.fold_in(kf, p), jax.random.fold_in(kn, p)))
            shape = (n_depos, sizes["patch_wires"], sizes["patch_ticks"])
            normals = np.asarray(jax.random.normal(kfp, shape, jnp.float32))
            k1, k2 = jax.random.split(knp)
            nw, nf = sizes["num_wires"], sizes["num_ticks"] // 2 + 1
            return normals, (np.asarray(jax.random.normal(k1, (nw, nf))),
                             np.asarray(jax.random.normal(k2, (nw, nf))))

    return reference.simulate_event(phys, draws, sizes, recon)


def event_view(batch_out: dict, i: int, sizes: dict) -> dict:
    """Event ``i`` of a batch as per-plane lists: the program's leaves are
    (E, W, T) for one plane and (E, P, W, T) for several; its hits become
    one dict of leaves per plane."""
    n = sizes["num_planes"]

    def planes(x):
        return [x[i]] if n == 1 else list(x[i])

    view = {k: planes(v) for k, v in batch_out.items() if k != "hits"}
    if batch_out.get("hits") is not None:
        leaves = {k: planes(v) for k, v in batch_out["hits"].items()}
        view["hits"] = [{k: v[p] for k, v in leaves.items()}
                        for p in range(n)]
    return view


def rel_l2(prog: List[np.ndarray], ref: List[np.ndarray]) -> float:
    """sqrt(sum_p ||prog_p - ref_p||^2) / sqrt(sum_p ||ref_p||^2)."""
    num = den = 0.0
    for a, b in zip(prog, ref, strict=True):
        d = a.astype(np.float64) - b
        num += np.sum(d * d)
        den += np.sum(b * b)
    return float(np.sqrt(num) / max(np.sqrt(den), 1e-30))


def mismatch_share(prog: List[np.ndarray], ref: List[np.ndarray]) -> float:
    """Pixels that differ over all pixels, across planes."""
    return float(np.mean(np.concatenate(
        [np.ravel(a != b) for a, b in zip(prog, ref, strict=True)])))


def _plane_hits(hits: dict):
    mask = hits["mask"]
    return hits["wire"][mask], hits["tick"][mask], hits["charge"][mask]


def hit_mismatch(prog_hits: List[dict], ref_hits: List[reference.Hits]
                 ) -> float:
    """Share of stored hits, on both sides, with no partner on the other."""
    unmatched = total = 0
    for plane, ref in zip(prog_hits, ref_hits, strict=True):
        pw, pt, pq = _plane_hits(plane)
        total += len(pw) + len(ref.wire)
        used = np.zeros(len(pw), bool)
        start = np.searchsorted(pw, ref.wire, side="left")
        stop = np.searchsorted(pw, ref.wire, side="right")
        for i in range(len(ref.wire)):
            cand = np.arange(start[i], stop[i])
            ok = cand[~used[cand]
                      & (np.abs(pt[cand] - ref.tick[i]) <= HIT_TICK_TOL)
                      & (np.abs(pq[cand] - ref.charge[i])
                         <= HIT_CHARGE_TOL * abs(ref.charge[i]))]
            if len(ok):
                used[ok[0]] = True
            else:
                unmatched += 1
        unmatched += int((~used).sum())
    return unmatched / max(total, 1)


def compare_event(prog: dict, ref: reference.EventRef) -> Dict[str, float]:
    """Numbers for one event; ``prog`` holds per-plane lists of host arrays
    (``event_view``)."""
    out = {
        "grid_err": rel_l2(prog["charge_grid"], ref.grid),
        "signal_err": rel_l2(prog["signal"], ref.signal),
        "adc_mismatch": mismatch_share(prog["adc"], ref.adc),
    }
    if ref.decon is not None:
        out["decon_err"] = rel_l2(prog["decon"], ref.decon)
        out["hit_mismatch"] = hit_mismatch(prog["hits"], ref.hits)
    return out


def compare_batch(batch_out: dict, event_ids: List[int], seed: int,
                  sizes: dict, n_depos: int, generator: str, recon: bool,
                  source) -> Dict[str, float]:
    """Worst number over the events of one batch of program outputs
    (``batch_out``: host arrays with a leading event axis), each event
    split by ``source.event_view`` and compared with
    ``source.reference_event``; the events' references run on threads of
    their own."""

    def one(i):
        prog = source.event_view(batch_out, i, sizes)
        ref = source.reference_event(seed, event_ids[i], sizes, n_depos,
                                     generator, recon)
        return compare_event(prog, ref)

    with ThreadPoolExecutor(max_workers=len(event_ids)) as pool:
        per_event = list(pool.map(one, range(len(event_ids))))
    worst: Dict[str, float] = {}
    for numbers in per_event:
        for k, v in numbers.items():
            worst[k] = max(worst.get(k, 0.0), v) if np.isfinite(v) else np.inf
    return worst


def verdict(numbers: Dict[str, float], limits: Dict[str, float]):
    """(correct, {name: {"value", "limit"}}): every limit must be met, and
    every number the cell has a limit for must have been read."""
    table = {name: {"value": numbers.get(name, float("nan")), "limit": lim}
             for name, lim in limits.items()}
    ok = all(np.isfinite(t["value"]) and t["value"] <= t["limit"]
             for t in table.values())
    return ok, table
