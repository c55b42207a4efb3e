"""The chip benchmark's own library: traffic, weights, work counts, peaks,
trace reduction, the served-path driver and the output check.

Nothing here is imported by the program under test; the program is
imported from ``src/`` only as the system being measured.
"""
