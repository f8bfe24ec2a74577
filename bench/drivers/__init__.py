"""Traffic generators, one file per kind, found by the traffic's `driver`."""
