"""The FPTC chip benchmark's own library: data, calibration, the plain
reference codec, work counts, peaks and the trace reduction.  Nothing here
is imported by the system under test, and nothing here imports it except
the drivers and the set-up that hand it data."""
