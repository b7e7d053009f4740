"""The benchmark's harness: cell lookup, traffic generation, the drivers of
each kind of cell, trace reduction and the comparison that decides
``correct``."""
