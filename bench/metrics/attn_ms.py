"""Model compute: device time per step of the operations under the
program's ``attn`` scope (QKV/O projections, RoPE, blockwise
attention; forward, backward and recompute), self time
(`harness.scopes`), in ms, on the first chip."""
from harness import scopes


def read(run):
    return scopes.scope_ms(run, ("attn",))
