"""Chip benchmark of the PGBJ kNN-join engine.

One run of one cell: ``python3 bench/run.py --workload <cell> --seed <n>
--seconds <s> --trace <0|1>``. Everything that belongs to one
configuration, traffic mix or per-layer metric lives in a file of its
own under this directory and is found by the name ``BENCHMARK.json``
gives it:

* ``configs/<config>.json``   the deployment (source, shapes, k, build);
* ``datagen/<generator>.py``  makes a configuration's data from a seed;
* ``builders/<builder>.py``   builds the system under test from the data;
* ``traffic/<mix>.json``      a traffic mix: its loop kind and parameters;
* ``loops/<loop>.py``         one module per loop kind;
* ``metrics/<metric>.py``     one reader per per-layer metric;
* ``references/<metric>.py``  the plain reference of a distance metric;
* ``peaks.json``              device peaks keyed by ``device_kind``.
"""
