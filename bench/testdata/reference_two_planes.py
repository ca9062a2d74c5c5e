"""A reference module for a readout whose two planes differ in wire count,
for the tests of the reference hook (``bench/test_bench_reference_hook.py``).

Plane 0 has 96 induction wires, plane 1 has 128 collection wires; both lie
at 0 degrees, at the configuration's pitch, and read its ticks. The depos
are drawn over the wider plane and scaled onto each plane's wires. Each
plane runs the plain reference's per-plane chain (``reference.plane_chain``)
by the default key schedule: fold_in(kf, p) and fold_in(kn, p), with noise
normals of the plane's own wire count.

The "program" this module reads stores an event's planes along one wire
axis, plane 0 first, (E, 96 + 128, T), and its hits with a plane axis,
(E, 2, max_hits); ``program_batch`` builds that layout from reference
outputs.
"""
import numpy as np

from bench import depogen, reference

WIRES = (96, 128)
KINDS = ("induction", "collection")
GENERATORS = depogen.GENERATORS


def reference_event(seed: int, event_id: int, sizes: dict, n_depos: int,
                    generator: str, recon: bool) -> reference.EventRef:
    import jax
    import jax.numpy as jnp

    key = depogen.event_key(seed, event_id)
    phys = GENERATORS[generator].draw(key, n_depos,
                                      dict(sizes, num_wires=max(WIRES)))
    phys = {f: np.asarray(getattr(phys, f)) for f in phys._fields}
    kf, kn = jax.random.split(key)
    nf = sizes["num_ticks"] // 2 + 1
    outs = []
    for p, (nw, kind) in enumerate(zip(WIRES, KINDS)):
        kfp, knp = jax.random.fold_in(kf, p), jax.random.fold_in(kn, p)
        shape = (n_depos, sizes["patch_wires"], sizes["patch_ticks"])
        normals = np.asarray(jax.random.normal(kfp, shape, jnp.float32))
        k1, k2 = jax.random.split(knp)
        noise = (np.asarray(jax.random.normal(k1, (nw, nf))),
                 np.asarray(jax.random.normal(k2, (nw, nf))))
        wire = phys["y"] * np.float32((nw - 1) / (max(WIRES) - 1))
        depos = reference.drift(dict(phys, wire=wire),
                                sizes["wire_pitch_mm"], sizes)
        outs.append(reference.plane_chain(depos, kind, nw, normals, noise,
                                          sizes, recon))
    return reference.event_ref(outs, recon)


def event_view(batch_out: dict, i: int, sizes: dict) -> dict:
    """Event ``i`` as per-plane lists: the arrays split along the wire
    axis, the hits along their plane axis."""
    cuts = np.cumsum(WIRES)[:-1]
    view = {k: np.split(v[i], cuts) for k, v in batch_out.items()
            if k != "hits"}
    if batch_out.get("hits") is not None:
        view["hits"] = [{k: v[i, p] for k, v in batch_out["hits"].items()}
                        for p in range(len(WIRES))]
    return view


def program_batch(refs: list, max_hits: int) -> dict:
    """Reference outputs of a batch of events in this program's layout."""
    out = {"charge_grid": np.stack([np.concatenate(r.grid) for r in refs]),
           "signal": np.stack([np.concatenate(r.signal) for r in refs]),
           "adc": np.stack([np.concatenate(r.adc) for r in refs])}
    if refs[0].decon is None:
        return out
    out["decon"] = np.stack([np.concatenate(r.decon) for r in refs])
    shape = (len(refs), len(WIRES), max_hits)
    hits = {"wire": np.zeros(shape, np.int32),
            "tick": np.zeros(shape, np.float32),
            "charge": np.zeros(shape, np.float32),
            "mask": np.zeros(shape, bool)}
    for e, r in enumerate(refs):
        for p, h in enumerate(r.hits):
            n = len(h.wire)
            hits["wire"][e, p, :n] = h.wire
            hits["tick"][e, p, :n] = h.tick
            hits["charge"][e, p, :n] = h.charge
            hits["mask"][e, p, :n] = True
    out["hits"] = hits
    return out
