"""Trainer step: device time per step of the operations under the
program's ``adamw`` scope (the optimizer update of every parameter and
both moments), self time (`harness.scopes`), in ms, on the first
chip."""
from harness import scopes


def read(run):
    return scopes.scope_ms(run, ("adamw",))
