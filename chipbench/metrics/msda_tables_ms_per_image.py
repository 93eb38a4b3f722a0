"""Device milliseconds per image of the MSDA op's XLA work around its
kernels, forward and backward: the corner tables (and their transpose
under autodiff), the value slabs and the cotangent's layout, the output
reduction and the unpacked gradients (``repro.obs.scopes.MSDA_XLA``;
``chipbench/device_scopes.py``)."""
from chipbench import device_scopes


def read(run):
    scopes = device_scopes.vocabulary()
    if not run.device_events() or scopes is None:
        return None
    xla = set(scopes.MSDA_XLA)
    seconds = sum(sec for names, _, sec in device_scopes.attributed(run)
                  if xla.intersection(names))
    return 1e3 * seconds / run.images
