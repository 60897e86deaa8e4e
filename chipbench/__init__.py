"""On-chip benchmark of the MA-Echo aggregation and serving system."""
