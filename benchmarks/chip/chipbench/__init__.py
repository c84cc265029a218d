"""The chip benchmark's own library: peaks, trace reduction, work counts,
arrivals, the file loader and the harness that runs one cell."""
