"""The benchmark's own library: cell lookup, device and peaks, on-device
data, trace reduction, needed-work functions and the comparisons that
decide ``correct``."""
