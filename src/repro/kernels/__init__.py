"""Pallas kernels for the chip, each tested against a pure-jnp function of
the same semantics: flash attention, the SSD chunked scan, the FRED
reduction tree and int8 block quantization.  No model calls them yet;
where one is wired in, the model chooses it from what it can observe (the
platform, the shapes), not from an option."""
