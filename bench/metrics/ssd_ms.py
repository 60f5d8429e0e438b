"""Model compute: device time per step of the operations under the
program's ``ssd`` scope (the chunked SSD scan of every Mamba-2 layer:
segment sums, decays, the four contractions and the inter-chunk scan;
forward, backward and recompute), self time (`harness.ssm_scopes`), in
ms, on the first chip."""
from harness import ssm_scopes


def read(run):
    return ssm_scopes.scope_ms(run, "ssd")
