"""Fused TPU (Pallas) kernels and their jnp oracles.

`quant_pack` holds the boundary-codec kernels (one HBM pass per wire
side), `ref` the bit-identical pure-jnp oracles, `ops` the
ragged-row-padding wrappers callers use, and `flash_attention` the
attention core's forward kernel and dK/dV, dQ backward pair, which the
model's `layers.flash_attention` runs on a TPU.  The kernels compile
through Mosaic on a TPU and run in interpret mode on every other
backend (`repro.env.pallas_interpret`).
"""
