"""Traffic drivers: one general generator per kind of traffic.  A
traffic file (`bench/traffic/<name>.json`) names its driver and holds
its parameters."""
