"""Model compute: device time per step of the operations under the
program's ``ffn`` scope (the MLP's up and down projections and
activation; forward, backward and recompute), self time
(`harness.scopes`), in ms, on the first chip."""
from harness import scopes


def read(run):
    return scopes.scope_ms(run, ("ffn",))
