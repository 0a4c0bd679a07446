"""On-chip benchmark of the IP2 saccade serving engine."""
