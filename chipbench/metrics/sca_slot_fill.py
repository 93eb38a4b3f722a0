"""Share of the packed slots of BEVFormer's spatial cross-attention that
hold a hit query: the cameras' hit queries over their static bounds, as
the program counted them into ``repro.obs.registry`` for the window's
frames (``bevformer.sca.hits`` and ``bevformer.sca.slots``, by camera).
Every slot past a camera's hits is padding its MSDA call still computes.
Reported where the configuration has cameras and the program counted
them.

A configuration's rig is fixed, so every frame has the same hits and the
bounds are set from them (``camera_bounds``): the reading is a property
of the configuration and moves only with the rig or the bound rule."""


def sca_counts(run):
    """``{camera: (hits, slots)}`` the program counted, or None."""
    if not run.cfg.get("num_cams"):
        return None
    try:
        from repro.obs import registry
    except ImportError:
        return None
    hits = registry.gauge("bevformer.sca.hits")
    slots = registry.gauge("bevformer.sca.slots")
    cams = {dict(k).get("camera") for k in hits.labels()}
    if not cams or None in cams:
        return None
    return {c: (hits.value(camera=c), slots.value(camera=c))
            for c in sorted(cams)}


def read(run):
    counts = sca_counts(run)
    if not counts:
        return None
    return (100.0 * sum(h for h, _ in counts.values())
            / sum(s for _, s in counts.values()))
