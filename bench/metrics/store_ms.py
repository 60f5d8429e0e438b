"""Boundary codec: device time per step of the operations under the
program's ``store`` scope (the per-sample gather of the stored
messages and their ``seen`` flags, and the scatter of the new ones),
self time (`harness.scopes`), in ms, on the first chip.  Copies the
compiler adds to change the whole store's layout carry no scope and
fall in `unscoped_ms`."""
from harness import scopes


def read(run):
    return scopes.scope_ms(run, ("store",))
