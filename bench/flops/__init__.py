"""Model FLOPs per trained token, one module per model family, each
with ``flops_per_token(config, seq)``.  Forward and backward, from the
configuration's widths; recomputation does not count."""
