"""The comparison that decides ``correct``.

After the window has closed, one batch of the window, drawn from the seed,
is compared event by event with the plain reference (``reference.py``),
stage by stage along the chain the timed program ran, each stage fed by the
reference's own previous stage:

    grid_err      charge grid after fluctuation  ||prog - ref|| / ||ref||
    signal_err    after convolution and noise    ||prog - ref|| / ||ref||
    adc_mismatch  digitized counts               share of pixels that differ
    decon_err     deconvolved charge (recon)     ||prog - ref|| / ||ref||
    hit_mismatch  stored hits (recon)            share without a partner

Norms run over all planes of an event; each number is the worst event of
the batch. A number passes when it is at most its limit from the cell's
file (``bench/cells/<cell>.json``); ``PERF.md`` gives the readings each
limit was set from.
"""
from __future__ import annotations

from concurrent.futures import ThreadPoolExecutor
from typing import Dict, List

import numpy as np

from bench import depogen, reference

#: a program hit partners a reference hit on the same wire when its mean
#: tick and charge agree this closely
HIT_TICK_TOL = 0.5
HIT_CHARGE_TOL = 0.05


def sample_batch(seed: int, n_batches: int) -> int:
    """The batch of the window that is compared, drawn from the seed."""
    return int(np.random.default_rng(seed).integers(n_batches))


def reference_event(seed: int, event_id: int, sizes: dict, n_depos: int,
                    generator: str, recon: bool) -> reference.EventRef:
    """The reference's outputs for one event of the stream ``seed``.

    The depos come from the frozen generator on the default device, as the
    program's stream draws them there; the normals from ``jax.random`` on
    the host CPU."""
    import jax
    import jax.numpy as jnp

    key = depogen.event_key(seed, event_id)
    phys = depogen.GENERATORS[generator](key, n_depos, sizes)
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


def rel_l2(prog: np.ndarray, ref: np.ndarray) -> float:
    d = prog.astype(np.float64) - ref
    return float(np.sqrt(np.sum(d * d)) / max(np.sqrt(np.sum(ref * ref)),
                                               1e-30))


def _plane_hits(hits: dict, p: int):
    mask = hits["mask"][p]
    return (hits["wire"][p][mask], hits["tick"][p][mask],
            hits["charge"][p][mask])


def hit_mismatch(prog_hits: dict, ref_hits: List[reference.Hits]) -> float:
    """Share of stored hits, on both sides, with no partner on the other."""
    unmatched = total = 0
    for p, ref in enumerate(ref_hits):
        pw, pt, pq = _plane_hits(prog_hits, p)
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
    """Numbers for one event; ``prog`` holds host arrays with a plane axis."""
    out = {
        "grid_err": rel_l2(prog["charge_grid"], ref.grid),
        "signal_err": rel_l2(prog["signal"], ref.signal),
        "adc_mismatch": float(np.mean(prog["adc"] != ref.adc)),
    }
    if ref.decon is not None:
        out["decon_err"] = rel_l2(prog["decon"], ref.decon)
        out["hit_mismatch"] = hit_mismatch(prog["hits"], ref.hits)
    return out


def compare_batch(batch_out: dict, event_ids: List[int], seed: int,
                  sizes: dict, n_depos: int, generator: str,
                  recon: bool) -> Dict[str, float]:
    """Worst number over the events of one batch of program outputs
    (``batch_out``: host arrays with a leading event axis); the events'
    references run on threads of their own."""

    def one(i):
        prog = _event_view(batch_out, i, sizes["num_planes"])
        ref = reference_event(seed, event_ids[i], sizes, n_depos, generator,
                              recon)
        return compare_event(prog, ref)

    with ThreadPoolExecutor(max_workers=len(event_ids)) as pool:
        per_event = list(pool.map(one, range(len(event_ids))))
    worst: Dict[str, float] = {}
    for numbers in per_event:
        for k, v in numbers.items():
            worst[k] = max(worst.get(k, 0.0), v) if np.isfinite(v) else np.inf
    return worst


def _event_view(batch_out: dict, i: int, num_planes: int) -> dict:
    """Event ``i`` of a batch, with a plane axis even for one plane."""

    def take(x):
        x = x[i]
        return x[None] if num_planes == 1 else x

    view = {k: take(v) for k, v in batch_out.items() if k != "hits"}
    if batch_out.get("hits") is not None:
        view["hits"] = {k: take(v) for k, v in batch_out["hits"].items()}
    return view


def verdict(numbers: Dict[str, float], limits: Dict[str, float]):
    """(correct, {name: {"value", "limit"}}): every limit must be met, and
    every number the cell has a limit for must have been read."""
    table = {name: {"value": numbers.get(name, float("nan")), "limit": lim}
             for name, lim in limits.items()}
    ok = all(np.isfinite(t["value"]) and t["value"] <= t["limit"]
             for t in table.values())
    return ok, table
