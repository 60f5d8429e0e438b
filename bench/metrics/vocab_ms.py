"""Model compute: device time per step of the vocabulary's operations,
the program's ``embed`` scope (token gather, its scatter-add gradient)
and ``lm_head`` scope (final norm, tied head matmul, log-sum-exp), self
time (`harness.scopes`), in ms, on the first chip."""
from harness import scopes


def read(run):
    return scopes.scope_ms(run, ("embed", "lm_head"))
