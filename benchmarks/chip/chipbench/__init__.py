"""The on-chip benchmark's own code: the yardstick the program is held to.

``registry`` finds a cell's configuration, traffic mix, limits, model and
metric readers by name; ``harness`` runs one cell; ``reference`` is the
plain per-round FedMom loop that decides ``correct``; ``trace`` reduces a
profiler trace to device intervals; ``peaks`` is the table of chip peaks.
Nothing here is imported by the program.
"""
