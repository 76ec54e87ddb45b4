"""The chip benchmark's yardstick: loading cells by name, the plain
reference, the comparison that decides ``correct``, the compile clock and
the trace reduction. Nothing in this package imports the program under
test; the program is driven from ``../drivers`` and set up in ``../run.py``.
"""
