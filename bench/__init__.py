"""On-chip benchmark of the streaming LArTPC simulator (see PERF.md)."""
