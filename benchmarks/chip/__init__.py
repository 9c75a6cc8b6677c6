"""On-chip benchmark of the cycle-level interconnect simulator."""
