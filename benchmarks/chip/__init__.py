"""The chip benchmark: one command that runs one cell of BENCHMARK.json.

Everything that belongs to one configuration, traffic mix or per-layer
metric sits in a file of its own, found by name:

* ``configs/<config>.json``: the configuration as run, its source and cuts;
* ``refs/<reference>.py``: the plain float32 reference a configuration names;
* ``counts/<config>.py``: operations counted from shapes;
* ``traffic/<traffic>.json``: the traffic mix and the driver that plays it;
* ``drivers/<driver>.py``: a general driver (``serve``);
* ``limits/<cell>.json``: the limits of the cell's correctness comparison;
* ``metrics/<metric>.py``: the reader of one per-layer metric.
"""
