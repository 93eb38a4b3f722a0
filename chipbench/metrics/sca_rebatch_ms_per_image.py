"""Device milliseconds per frame of BEVFormer's spatial cross-attention
re-batch: the pillars' projection into the cameras, the hit mask, the
packing of each camera's hit queries to its static bound, the scatter
back and the division by the cameras hit (``repro.obs.scopes``
``sca_rebatch``; ``chipbench/device_scopes.py``).  Not reported for a
program without that scope."""
from chipbench import device_scopes


def read(run):
    scopes = device_scopes.vocabulary()
    name = getattr(scopes, "SCA_REBATCH", None)
    if not run.device_events() or name is None:
        return None
    seconds = sum(sec for names, _, sec in device_scopes.attributed(run)
                  if name in names)
    return 1e3 * seconds / run.images
