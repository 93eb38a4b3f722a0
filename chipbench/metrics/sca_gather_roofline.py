"""Share of the roofline of BEVFormer's spatial-cross-attention gathers:
the least time the chip could take for the MSDA forward over the hit
queries alone (``work.msda_fwd_work`` at batch 1 per camera and encoder
layer, each camera's hits as the program counted them into
``repro.obs.registry``, gauge ``bevformer.sca.hits``) over the device
time of the ``msda_gather`` kernel events under the ``bev_sca`` scope
(``chipbench/device_scopes.py``).  The packed slots past a camera's hits
are computed but count as no work, so padding shows as lost share."""
from chipbench import device_scopes, work


def camera_hits(run):
    """Each camera's hit queries as the program counted them, or None."""
    if not run.cfg.get("num_cams"):
        return None
    try:
        from repro.obs import registry
    except ImportError:
        return None
    g = registry.gauge("bevformer.sca.hits")
    cams = [dict(k).get("camera") for k in g.labels()]
    if not cams or None in cams:
        return None
    return [g.value(camera=c) for c in cams]


def read(run):
    scopes = device_scopes.vocabulary()
    sca = getattr(scopes, "BEV_SCA", None)
    hits = camera_hits(run)
    if run.peak is None or not run.device_events() or sca is None or not hits:
        return None
    cfg = run.cfg
    levels = [tuple(l) for l in cfg["levels"]]
    dtypes = {c.plan.spec.dtype for c in run.msda
              if [tuple(l) for l in c.plan.spec.spatial_shapes] == levels}
    if len(dtypes) != 1:
        return None
    dtype = dtypes.pop()
    flops = nbytes = 0.0
    for n in hits:
        f, b = work.msda_fwd_work(levels, 1, int(n), cfg["num_heads"],
                                  cfg["head_dim"], cfg["sca_points"], dtype)
        flops, nbytes = flops + f, nbytes + b
    calls = cfg["encoder_layers"] * run.calls
    least, _ = work.least_seconds(flops * calls, nbytes * calls, run.peak)
    seconds = sum(sec for names, _, sec in device_scopes.attributed(run)
                  if sca in names and scopes.GATHER_KERNEL in names)
    return 100.0 * least / seconds if seconds else None
