"""Per-layer metric readers.  layers/<metric>.json names one and gives
its parameters; read(window, trace, devices, **params) returns the value,
or None where the run has nothing for it to read."""
