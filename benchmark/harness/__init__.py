"""The benchmark's own code: resolution by name, traffic, trace reduction,
the comparison that decides ``correct``. Nothing here names a model or a
cell; what belongs to one lives in a file found by its name."""
