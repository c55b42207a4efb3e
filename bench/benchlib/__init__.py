"""The chip benchmark's own library: traffic, weights, the work counts every
architecture shares, peaks, trace reduction, the served-path driver and the
output check.  What belongs to one architecture is in the module its
configuration file names (``bench/configs/``).

Nothing here is imported by the program under test; the program is
imported from ``src/`` only as the system being measured.
"""
