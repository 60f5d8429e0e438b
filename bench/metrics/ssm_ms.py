"""Model compute: device time per step of the operations under the
program's ``ssm`` scope outside its ``ssd`` scope (each Mamba-2
layer's norm, in and out projections, causal conv, softplus, D skip,
gate and gated norm, and the slices of its stacked weights; forward,
backward and recompute), self time (`harness.ssm_scopes`), in ms, on
the first chip."""
from harness import ssm_scopes


def read(run):
    return ssm_scopes.scope_ms(run, "ssm")
