"""The on-chip benchmark's own code: the yardstick that later changes to
the program are measured with.  It imports the program (``repro``) only
as the system under test, in :mod:`chipbench.harness`."""
