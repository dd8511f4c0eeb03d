"""The chip benchmark's own code: everything that decides what a number means.

Later changes to the program cannot move this yardstick: the peaks, the
operation and byte counts, the trace reduction, the traffic generator and
the plain references live here and import nothing of the program except
where a module says so (the runners drive the system under test).
"""
