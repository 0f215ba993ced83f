"""On-chip benchmark of the binarized serving stack.

``python bench/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>``
runs one cell of ``BENCHMARK.json`` on the TPU it is started on and prints
one JSON result line. Everything that belongs to one configuration, traffic
mix or per-layer metric is a file of its own, found by name:

* ``configs/<config>.json``  sizes, source and departures of a configuration;
  its ``system`` names the driver in ``systems/`` and its ``reference`` the
  plain float32 model in ``reference/``;
* ``traffic/<cell>.json``    one cell's traffic mix and serving geometry; its
  ``generator`` names the mix kind in ``traffic/<generator>.py``;
* ``metrics/<metric>.py``    one per-layer metric's reader;
* ``work/``                  operations and bytes per kernel call, step and
  forward, from logical shapes; ``peaks.json`` the chip's peaks;
* ``trace/``                 the reduction from ``.xplane.pb`` to device
  intervals, kernel sums and idle gaps by host span.
"""
