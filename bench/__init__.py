"""On-chip benchmark of the Kant scheduler's device score path.

One command runs one cell (a cluster configuration under a traffic mix)
once::

    python3 -m bench.run --workload kant-10k.train-steady --seed 7 \\
        --seconds 10 --trace 0

Everything a cell needs is found by name: ``BENCHMARK.json`` names the
cell, ``bench/configs/<config>.json`` holds the cluster, the
``bench/traffic/<traffic>.json`` file (overlaid by
``bench/traffic/<traffic>/<config>.json`` where it exists) holds the
traffic, and ``bench/metrics/<metric>.py`` reads each metric.  See
``bench/README.md``.
"""
