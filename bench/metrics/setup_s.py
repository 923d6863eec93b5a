"""Seconds from the process's start to the window's: imports, weights,
engine, compilation (or the cache's load), and the traffic set-up asks
for (contexts prefilled, or the open loop warmed)."""


def read(run):
    return run.setup_s
