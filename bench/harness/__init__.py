"""The benchmark's general code: the spec, the chip check, the traffic
generator, the trace reduction, the HLO counts and the comparison that
decides ``correct``.  Nothing here names a configuration, a traffic mix
or a metric: those are files found by name (see `harness.spec`)."""
