"""On-chip benchmark of the paged GVR decode engine (see bench/README.md)."""
