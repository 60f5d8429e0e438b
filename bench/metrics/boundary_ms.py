"""Boundary codec: device time per step of the operations under the
program's ``boundary`` scope (the sender's delta encode, the stored
message update, the backward gradient's codec, the noise), the codec
kernels included, so at least `codec_ms`; self time
(`harness.scopes`), in ms, on the first chip."""
from harness import scopes


def read(run):
    return scopes.scope_ms(run, ("boundary",))
