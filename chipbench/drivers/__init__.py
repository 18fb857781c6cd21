"""Traffic drivers: each runs one kind of traffic mix against one user
path, found by the mix's ``driver`` key."""
