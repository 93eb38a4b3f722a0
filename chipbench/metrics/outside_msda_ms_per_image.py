"""Device milliseconds per image of the model step outside the MSDA op:
events under a known scope of the program but under neither
``msda_fwd`` nor ``msda_bwd`` (projections, FFNs, norms, decoder
self-attention, heads, matching and loss, optimizer;
``chipbench/device_scopes.py``)."""
from chipbench import device_scopes


def read(run):
    scopes = device_scopes.vocabulary()
    if not run.device_events() or scopes is None:
        return None
    msda = set(scopes.MSDA_OPS)
    seconds = sum(sec for names, _, sec in device_scopes.attributed(run)
                  if names and not msda.intersection(names))
    return 1e3 * seconds / run.images
