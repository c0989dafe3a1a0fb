"""The benchmark's own code: traffic, weights, the plain reference, the
drivers, the reduction from traces to metrics, and the comparison that
decides ``correct``. Nothing here is imported by the program, and only the
two drivers import the program."""
