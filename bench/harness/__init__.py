"""The benchmark's yardstick: fleet synthesis, the task stream every task
mix draws (the mixes themselves are in ``bench/tasks/``), the traffic
generator, latency arithmetic, trace reduction, the kernel bytes model and
the comparison that decides ``correct``.  None of it imports the program
except :mod:`harness.system`, which wraps the served path under test."""
